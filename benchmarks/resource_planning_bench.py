"""Resource-planning overhead benchmark (paper Figs 13/14 + §VII-C scale).

Reproduces the paper's overhead-reduction table for one join operator's
resource planning on the §VII evaluation cluster (100 containers x 10 GB),
comparing:

    brute_scalar   one Python cost call per configuration (the seed's path)
    hillclimb      Algorithm 1 (§VI-B2)
    cached         resource-plan cache hit (§VI-B3, warm NN cache)
    batched        vectorized full-grid scan via cost_grid (this repo's
                   batched costing backend)

then compares the numpy, jax, and pallas ``PlanBackend`` implementations
— grid scan and multi-start ensemble climb — on both the paper grid and
the §VII-C scalability grid (``scaled_cluster(100_000, 100)`` = 10M
configurations, intractable for the scalar path at ~10M Python calls per
operator), the ``pallas`` section: the fused scan+argmin kernel
(repro.kernels.plan_scan) against the jitted jax chunk scan, single
request and (Q, P)-stacked, with zero materialized ``(Q, chunk)`` cost
matrix, and finally the ``multi_query`` section: the session planning
broker (repro.core.plan_broker) planning a 32-operator / 8-query batch
over the scaled grid against the per-operator jitted baseline (one
program dispatch per request) — the broker dedups recurring operators
and stacks the rest into one vmapped program per cost model.

Two sections cover the multi-device execution layer: ``sharded`` runs
the scaled-grid scan in one SUBPROCESS per simulated device count
(``XLA_FLAGS`` must precede the first jax import), recording scan rate
vs 1/2/4/8 devices plus bit-identity of every argmin against the numpy
oracle, and ``overlap`` times the 8-query Selinger workload through the
double-buffered broker (``flush_async``: wave N executes on device
while wave N+1 enumerates) against the serial-flush path.  Wall-clock
speedups for either need real parallel cores: on a single-core host
simulated devices time-slice one CPU and the overlap has nothing to
overlap with, so the monotonic-scaling and overlap-win checks are
reported, and gated only when ``os.cpu_count()`` can express them.

    PYTHONPATH=src python -m benchmarks.resource_planning_bench
    PYTHONPATH=src python -m benchmarks.resource_planning_bench --quick

``--quick`` shrinks the scaled grid and repeat counts for CI smoke runs
(no wall-clock assertions; the tracked JSON is left untouched so shrunken
grids never pollute the trend).  Each full run *appends* a summary
snapshot to the ``history`` list inside BENCH_resource_planning.json so
the perf trajectory is tracked across PRs; standalone main() asserts the
acceptance properties: batched == scalar argmin on the paper cluster,
>= 10x wall-clock reduction for brute-force planning, jax >= numpy on
the scaled grid scan, and >= 2x for the jax ensemble climb vs the
2-start batched climb.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import List, Optional, Tuple

from repro.core.cluster import paper_cluster, scaled_cluster
from repro.core.cost_model import simulator_cost_models
from repro.core.hillclimb import brute_force, hill_climb, hill_climb_multi
from repro.core.plan_broker import PlanBroker
from repro.core.plan_cache import ResourcePlanCache
from repro.core.plans import OperatorCosting
from repro.core.raqo import RAQO
from repro.core.schema import random_query, random_schema
from repro.core.selinger import selinger_plan
from repro.launch.compile_cache import enable_compile_cache

Row = Tuple[str, float, str]

# one representative join operator (TPC-H-ish sizes, §III's profiled regime)
OPERATOR = {"impl": "SMJ", "ss": 2.0, "ls": 74.0}
REPEATS = 5
ENSEMBLE_STARTS = 24

# ----- multi-query workload (broker benchmark) ------------------------------ #
# Recurring query templates (the paper's §V story: most production jobs
# are recurring): 8 concurrent queries of 4 operators each — 32 planning
# requests over 9 distinct operator characteristics, so a per-operator
# planner searches 32 times while the session broker searches 9, stacked
# into 2 array programs (one per cost model).  Ops within a query are
# distinct (the per-query memo can't help the baseline).
MQ_UNIQUE = [("SMJ", 0.5 + 0.75 * i, 50.0 + 12.0 * i) for i in range(5)] + \
            [("BHJ", 0.4 + 0.45 * i, 40.0 + 18.0 * i) for i in range(4)]
MQ_QUERIES = [[MQ_UNIQUE[(q * 4 + k) % len(MQ_UNIQUE)] for k in range(4)]
              for q in range(8)]


def _costing(cluster, mode: str, cache=None, objective: str = "time",
             backend=None) -> OperatorCosting:
    return OperatorCosting(models=simulator_cost_models(), cluster=cluster,
                           resource_planning=mode, cache=cache,
                           objective=objective, backend=backend,
                           ensemble_starts=ENSEMBLE_STARTS)


def _backends() -> List[str]:
    """numpy + whatever accelerated backends construct on this host."""
    from repro.core.planning_backend import have_backend
    return ["numpy"] + [be for be in ("jax", "pallas") if have_backend(be)]


def _time_plan_resources(costing: OperatorCosting,
                         repeats: int = REPEATS
                         ) -> Tuple[float, Optional[Tuple[int, ...]]]:
    """Best wall-clock of ``plan_resources`` over ``repeats`` runs (memo
    cleared between runs; jit compile time amortized out by best-of)."""
    impl, ss, ls = OPERATOR["impl"], OPERATOR["ss"], OPERATOR["ls"]
    best_t, res = math.inf, None
    for _ in range(repeats):
        costing.begin_query()
        t0 = time.perf_counter()
        res, _ = costing.plan_resources(impl, ss, ls)
        best_t = min(best_t, time.perf_counter() - t0)
    return best_t, res


def _time_plan(costing: OperatorCosting, *, batch: bool,
               repeats: int = REPEATS) -> Tuple[float, Tuple[int, ...]]:
    """Best wall-clock seconds over ``repeats`` runs of one operator's
    resource planning, memo cleared between runs so every run searches."""
    impl, ss, ls = OPERATOR["impl"], OPERATOR["ss"], OPERATOR["ls"]
    fn = lambda res: costing._op_cost_at(impl, ss, ls, res)     # noqa: E731
    batch_fn = costing._batch_fn(impl, ss, ls) if batch else None
    best_t, res = math.inf, None
    for _ in range(repeats):
        costing.begin_query()
        t0 = time.perf_counter()
        if costing.resource_planning in ("brute", "batched"):
            res, _ = brute_force(fn, costing.cluster, costing.stats,
                                 batch_cost_fn=batch_fn)
        elif costing.resource_planning == "hillclimb_batched":
            res, _ = hill_climb_multi(fn, costing.cluster,
                                      stats=costing.stats,
                                      batch_cost_fn=batch_fn)
        else:
            res, _ = hill_climb(fn, costing.cluster, stats=costing.stats)
        best_t = min(best_t, time.perf_counter() - t0)
    return best_t, res


def overhead_table() -> Tuple[List[Row], dict]:
    """The Fig 13/14-style overhead table on paper_cluster(100, 10)."""
    cluster = paper_cluster(100, 10)
    rows: List[Row] = []
    out = {}

    t_scalar, res_scalar = _time_plan(_costing(cluster, "brute"), batch=False)
    t_batched, res_batched = _time_plan(_costing(cluster, "batched"),
                                        batch=True)
    t_hc, res_hc = _time_plan(_costing(cluster, "hillclimb"), batch=False)
    t_hcb, _ = _time_plan(_costing(cluster, "hillclimb_batched"), batch=True)

    # warm NN cache -> per-operator planning is one lookup + one cost call
    cache = ResourcePlanCache("nearest_neighbor", threshold=0.1)
    costing_c = _costing(cluster, "hillclimb", cache=cache)
    costing_c.plan_resources(OPERATOR["impl"], OPERATOR["ss"], OPERATOR["ls"])
    t_cached = math.inf               # best-of-REPEATS, like _time_plan
    for _ in range(REPEATS):
        costing_c.begin_query()       # memo off; measure the cache path
        t0 = time.perf_counter()
        costing_c.plan_resources(OPERATOR["impl"], OPERATOR["ss"],
                                 OPERATOR["ls"])
        t_cached = min(t_cached, time.perf_counter() - t0)

    assert res_batched == res_scalar, \
        f"batched argmin {res_batched} != scalar argmin {res_scalar}"

    for name, t in (("brute_scalar", t_scalar), ("hillclimb", t_hc),
                    ("hillclimb_batched", t_hcb), ("cached", t_cached),
                    ("batched", t_batched)):
        rows.append((f"resplan.paper_cluster.{name}_us", t * 1e6,
                     "per-operator resource planning wall time"))
        out[name + "_us"] = t * 1e6
    speedup = t_scalar / t_batched
    rows.append(("resplan.paper_cluster.batched_speedup_x", speedup,
                 "brute-force scalar / batched wall-clock (target >= 10)"))
    out["batched_speedup_x"] = speedup
    out["configs"] = cluster.grid_size()
    out["scalar_config"] = list(res_scalar)
    out["batched_config"] = list(res_batched)
    out["hillclimb_config"] = list(res_hc)
    return rows, out


def scalability(quick: bool = False) -> Tuple[List[Row], dict]:
    """§VII-C: full brute-force plan on the 100K x 100 grid (10M configs)."""
    cluster = scaled_cluster(1_000, 20) if quick \
        else scaled_cluster(100_000, 100)
    costing = _costing(cluster, "batched")
    impl, ss, ls = OPERATOR["impl"], OPERATOR["ss"], OPERATOR["ls"]
    t0 = time.perf_counter()
    res, cost = costing.plan_resources(impl, ss, ls)
    dt = time.perf_counter() - t0
    tag = "scaled_1kx20" if quick else "scaled_100kx100"
    rows = [
        (f"resplan.{tag}.batched_s", dt,
         f"brute-force over {cluster.grid_size():,} configs -> r={res} "
         f"(target < 5s)"),
        (f"resplan.{tag}.configs", float(cluster.grid_size()),
         "grid points"),
    ]
    return rows, {"batched_s": dt, "configs": cluster.grid_size(),
                  "config": list(res), "cost_s": cost}


def backend_table(quick: bool = False) -> Tuple[List[Row], dict]:
    """numpy-vs-jax PlanBackend comparison: full-grid scan on the paper
    grid and the scaled grid, plus the vectorized multi-start ensemble
    climb against the 2-start batched climb (the ROADMAP open item the
    ensemble fixes)."""
    repeats = 2 if quick else REPEATS
    paper = paper_cluster(100, 10)
    scaled = scaled_cluster(1_000, 20) if quick \
        else scaled_cluster(100_000, 100)
    rows: List[Row] = []
    out: dict = {"ensemble_starts": ENSEMBLE_STARTS,
                 "scaled_configs": scaled.grid_size()}
    backends = _backends()

    t_2start, _ = _time_plan_resources(
        _costing(paper, "hillclimb_batched"), repeats)
    rows.append(("resplan.backend.hillclimb_batched_2start_us",
                 t_2start * 1e6, "2-corner-start batched climb (baseline)"))
    out["hillclimb_batched_2start_us"] = t_2start * 1e6

    configs = {}
    for be in backends:
        t_scan, res_scan = _time_plan_resources(
            _costing(paper, "batched", backend=be), repeats)
        t_scaled, res_scaled = _time_plan_resources(
            _costing(scaled, "batched", backend=be), repeats)
        t_ens, res_ens = _time_plan_resources(
            _costing(paper, "ensemble", backend=be), repeats)
        configs[be] = {"scan": res_scan, "scaled": res_scaled,
                       "ensemble": res_ens}
        rows += [
            (f"resplan.backend.{be}.paper_scan_us", t_scan * 1e6,
             f"full 1000-point grid scan -> r={res_scan}"),
            (f"resplan.backend.{be}.scaled_scan_s", t_scaled,
             f"full {scaled.grid_size():,}-point grid scan -> "
             f"r={res_scaled}"),
            (f"resplan.backend.{be}.ensemble_us", t_ens * 1e6,
             f"{ENSEMBLE_STARTS}+2-start ensemble climb -> r={res_ens}"),
        ]
        out[be] = {"paper_scan_us": t_scan * 1e6, "scaled_scan_s": t_scaled,
                   "ensemble_us": t_ens * 1e6}
    # cross-backend argmin agreement is recorded, not asserted, inside
    # run() (a float32 near-tie must not abort the benchmarks/run.py
    # sweep); main() enforces it standalone
    for be in backends[1:]:
        out[be]["argmin_match"] = float(
            configs[be]["scan"] == configs["numpy"]["scan"]
            and configs[be]["scaled"] == configs["numpy"]["scaled"])
        rows.append((f"resplan.backend.{be}.argmin_match",
                     out[be]["argmin_match"],
                     f"{be} argmins == numpy argmins (1 = agree)"))
    if "jax" in configs:
        out["argmin_match"] = out["jax"]["argmin_match"]
        rows.append(("resplan.backend.argmin_match", out["argmin_match"],
                     "jax argmins == numpy argmins (1 = agree)"))
        out["scaled_jax_vs_numpy_x"] = \
            out["numpy"]["scaled_scan_s"] / out["jax"]["scaled_scan_s"]
        out["ensemble_vs_2start_x"] = \
            out["hillclimb_batched_2start_us"] / out["jax"]["ensemble_us"]
        rows += [
            ("resplan.backend.scaled_jax_vs_numpy_x",
             out["scaled_jax_vs_numpy_x"],
             "numpy / jax scaled-grid scan wall-clock (target >= 1)"),
            ("resplan.backend.ensemble_vs_2start_x",
             out["ensemble_vs_2start_x"],
             "2-start batched climb / jax ensemble climb (target >= 2)"),
        ]
    return rows, out


def pallas_table(quick: bool, backends_out: dict) -> Tuple[List[Row], dict]:
    """The fused-kernel section (repro.kernels.plan_scan): the pallas
    scan+argmin kernel against the jitted jax chunk scan on the 10M-point
    grid (the ROADMAP's last open kernel item) — single request and the
    (Q, P)-stacked scan the broker's flush groups run, measured directly
    on the backend primitives with interleaved best-of repeats.  The
    pallas side materializes no (Q, chunk) cost matrix: each kernel
    program reduces its own (block,) cost vector in VMEM."""
    rows: List[Row] = []
    out: dict = {}
    if "pallas" not in backends_out or "jax" not in backends_out:
        return rows, out
    from repro.core.planning_backend import get_backend
    cluster = scaled_cluster(1_000, 20) if quick \
        else scaled_cluster(100_000, 100)
    model = simulator_cost_models()["SMJ"]

    def _fn_for(be):
        def fn(cfgs, p, xp=be.xp):
            return model.cost_grid(p[0], p[1], cfgs, xp=xp)
        return fn

    # single-request scan, measured head-to-head on the backend
    # primitives with INTERLEAVED best-of repeats (back-to-back pairs
    # cancel host-load drift that separate timing sections pick up)
    params = [OPERATOR["ss"], OPERATOR["ls"]]
    fns = {}
    scan_t = {}
    for be_name in ("jax", "pallas"):
        be = get_backend(be_name)
        fns[be_name] = _fn_for(be)
        be.argmin_grid(fns[be_name], cluster, params=params)  # warm-up
        scan_t[be_name] = math.inf
    for _ in range(3 if quick else 7):
        for be_name in ("jax", "pallas"):
            t0 = time.perf_counter()
            get_backend(be_name).argmin_grid(fns[be_name], cluster,
                                             params=params)
            scan_t[be_name] = min(scan_t[be_name],
                                  time.perf_counter() - t0)
    out["jax_scan_s"] = scan_t["jax"]
    out["pallas_scan_s"] = scan_t["pallas"]
    out["vs_jax_scan_x"] = scan_t["jax"] / scan_t["pallas"]
    out["argmin_match"] = backends_out["pallas"]["argmin_match"]
    rows += [
        ("resplan.pallas.jax_scan_s", out["jax_scan_s"],
         f"jitted jax chunk scan, {cluster.grid_size():,}-point grid"),
        ("resplan.pallas.pallas_scan_s", out["pallas_scan_s"],
         f"fused pallas scan+argmin kernel, {cluster.grid_size():,}-point "
         "grid"),
        ("resplan.pallas.vs_jax_scan_x", out["vs_jax_scan_x"],
         f"jitted jax chunk scan / fused pallas kernel, "
         f"{cluster.grid_size():,}-point grid (target >= 1; gated on "
         "the full grid only — dispatch overhead dominates the tiny "
         "--quick grid)"),
        ("resplan.pallas.argmin_match", out["argmin_match"],
         "pallas argmins == numpy argmins (1 = agree)"),
    ]

    # (Q, P)-stacked scan: one fn, Q per-request (ss, ls) params — the
    # broker flush-group shape, run straight on the backend primitives
    pm = [[0.5 + 0.75 * i, 50.0 + 12.0 * i] for i in range(8)]
    out["many_q"] = len(pm)
    plans = {}
    many_t = {}
    for be_name in ("jax", "pallas"):
        be = get_backend(be_name)
        be.argmin_grid_many(fns[be_name], cluster, pm)  # compile warm-up
        many_t[be_name] = math.inf
    for _ in range(2 if quick else 3):
        for be_name in ("jax", "pallas"):               # interleaved
            t0 = time.perf_counter()
            plans[be_name] = get_backend(be_name).argmin_grid_many(
                fns[be_name], cluster, pm)
            many_t[be_name] = min(many_t[be_name],
                                  time.perf_counter() - t0)
    for be_name in ("jax", "pallas"):
        out[f"{be_name}_many_s"] = many_t[be_name]
        rows.append((f"resplan.pallas.{be_name}_many_s", many_t[be_name],
                     f"{len(pm)}-request stacked scan, "
                     f"{cluster.grid_size():,}-point grid"))
    out["many_vs_jax_x"] = out["jax_many_s"] / out["pallas_many_s"]
    out["many_match"] = float([p[0] for p in plans["pallas"]]
                              == [p[0] for p in plans["jax"]])
    rows += [
        ("resplan.pallas.many_vs_jax_x", out["many_vs_jax_x"],
         "jax vmapped stacked scan / pallas (query, block)-grid kernel"),
        ("resplan.pallas.many_match", out["many_match"],
         "stacked pallas argmins == stacked jax argmins (1 = agree)"),
    ]
    return rows, out


def _run_per_op(costing: OperatorCosting) -> List[Tuple]:
    """The per-operator baseline: plan each query's operators one request
    (= one search / one program dispatch) at a time, per-query memo only."""
    out = []
    for q in MQ_QUERIES:
        costing.begin_query()
        out += [costing.plan_resources(impl, ss, ls) for impl, ss, ls in q]
    return out


def _run_broker(costing: OperatorCosting) -> List[Tuple]:
    """The broker path: queue every operator of every query, then resolve
    — the first resolve flushes the whole session as stacked programs."""
    for q in MQ_QUERIES:
        costing.begin_query()
        for impl, ss, ls in q:
            costing.prefetch(impl, ss, ls)
    out = []
    for q in MQ_QUERIES:
        costing.begin_query()
        out += [costing.plan_resources(impl, ss, ls) for impl, ss, ls in q]
    return out


def multi_query(quick: bool = False) -> Tuple[List[Row], dict]:
    """Session-broker vs per-operator planning for a multi-query batch
    (32 operators, 9 distinct) over the §VII-C scalability grid: the
    broker dedups recurring operators against its session memo and stacks
    the distinct ones into one vmapped jitted program per cost model,
    where the per-operator baseline dispatches one program per request."""
    cluster = scaled_cluster(1_000, 20) if quick \
        else scaled_cluster(100_000, 100)
    n_ops = sum(len(q) for q in MQ_QUERIES)
    n_unique = len({op for q in MQ_QUERIES for op in q})
    rows: List[Row] = []
    out: dict = {"ops": n_ops, "unique_ops": n_unique,
                 "queries": len(MQ_QUERIES), "configs": cluster.grid_size()}

    # batch-cost fns shared across repeats and paths (exactly how RAQO
    # shares them across queries): compiled search programs are keyed by
    # fn identity, so best-of-repeats measures steady state, not tracing
    shared_fns: dict = {}

    def costing(broker=None, backend=None, cache=None):
        return OperatorCosting(models=simulator_cost_models(),
                               cluster=cluster, resource_planning="batched",
                               backend=backend, broker=broker, cache=cache,
                               _grid_fn_cache=shared_fns)

    plans = {}
    for be in _backends():
        # warm-up + best-of timed repeats so jit compile time (paid once
        # per session fleet) is amortized out of the steady-state number
        repeats = 1 if be == "numpy" else (2 if quick else 3)
        t_per_op = t_broker = math.inf
        for _ in range(repeats + (0 if be == "numpy" else 1)):
            c = costing(backend=be)
            t0 = time.perf_counter()
            plans[be, "per_op"] = _run_per_op(c)
            t_per_op = min(t_per_op, time.perf_counter() - t0)
        for _ in range(repeats + (0 if be == "numpy" else 1)):
            broker = PlanBroker(backend=be)      # fresh session: no memo
            c = costing(broker=broker)
            t0 = time.perf_counter()
            plans[be, "broker"] = _run_broker(c)
            t_broker = min(t_broker, time.perf_counter() - t0)
            out.setdefault(be, {})["broker_stats"] = {
                "requests": broker.stats.broker_requests,
                "dedup_hits": broker.stats.broker_dedup_hits,
                "batches": broker.stats.broker_batches,
            }
        out[be].update({"per_op_s": t_per_op, "broker_s": t_broker,
                        "speedup_x": t_per_op / t_broker})
        rows += [
            (f"resplan.multi_query.{be}.per_op_s", t_per_op,
             f"{n_ops} per-operator searches, one program call each"),
            (f"resplan.multi_query.{be}.broker_s", t_broker,
             f"session broker: {n_unique} searches in stacked programs"),
            (f"resplan.multi_query.{be}.speedup_x", t_per_op / t_broker,
             "per-operator / broker wall-clock (jax target >= 3)"),
        ]

    # the numpy broker must be bit-identical (plans AND costs) with the
    # per-operator loop — recorded as a metric, asserted by main()
    out["numpy"]["identical"] = float(
        plans["numpy", "broker"] == plans["numpy", "per_op"])
    rows.append(("resplan.multi_query.numpy.identical",
                 out["numpy"]["identical"],
                 "numpy broker plans+costs == per-operator (1 = identical)"))
    for be in _backends()[1:]:
        if (be, "broker") not in plans:
            continue
        # the broker-parity property: stacked search == per-operator
        # search (same float32 arithmetic, stacked vs sequential)
        out[be]["broker_match"] = float(
            [p[0] for p in plans[be, "broker"]]
            == [p[0] for p in plans[be, "per_op"]])
        # informational: float32 near-ties vs float64 can break either
        # way on a 10M-point grid (the planners re-commit through f64)
        out[be]["argmin_match"] = float(
            [p[0] for p in plans[be, "broker"]]
            == [p[0] for p in plans["numpy", "per_op"]])
        rows += [
            (f"resplan.multi_query.{be}.broker_match",
             out[be]["broker_match"],
             f"{be} broker argmins == {be} per-operator (1 = agree)"),
            (f"resplan.multi_query.{be}.argmin_match",
             out[be]["argmin_match"],
             f"{be} broker argmins == numpy per-operator (1 = agree)"),
        ]

    # cache-fronted broker: the dedup win measured by the per-(model,
    # kind) hit/miss/insert counters (satellite of the broker PR)
    cache = ResourcePlanCache("exact")
    broker = PlanBroker(backend="numpy")
    _run_broker(costing(broker=broker, cache=cache))
    out["cache_counters"] = cache.counters_snapshot()
    out["cache_broker_stats"] = {
        "requests": broker.stats.broker_requests,
        "dedup_hits": broker.stats.broker_dedup_hits,
        "batches": broker.stats.broker_batches,
    }
    return rows, out


# ----- device-sharded scan scaling (subprocess lanes) ----------------------- #
# XLA fixes the host device count at first import, so each device count
# gets its own child interpreter; the child times the jax backend's
# sharded scan and checks its argmin against an in-child numpy oracle.

_SHARDED_DRIVER = """
import json, math, sys, time
import numpy as np
import jax
from repro.core.cluster import scaled_cluster
from repro.core.cost_model import simulator_cost_models
from repro.core.planning_backend import get_backend

want, quick, repeats = int(sys.argv[1]), sys.argv[2] == "1", int(sys.argv[3])
assert jax.device_count() == want, (jax.device_count(), want)
cluster = scaled_cluster(1_000, 20) if quick else scaled_cluster(100_000, 100)
model = simulator_cost_models()["SMJ"]
params = [float(sys.argv[4]), float(sys.argv[5])]
be = get_backend("jax")
assert be.device_count() == want, (be.device_count(), want)


def fn(cfgs, p, xp=be.xp):
    return model.cost_grid(p[0], p[1], cfgs, xp=xp)


res, _ = be.argmin_grid(fn, cluster, params=params)   # compile warm-up
best = math.inf
for _ in range(repeats):
    t0 = time.perf_counter()
    res, _ = be.argmin_grid(fn, cluster, params=params)
    best = min(best, time.perf_counter() - t0)


def fn_np(cfgs, p):
    return model.cost_grid(p[0], p[1], cfgs, xp=np)


res_np, _ = get_backend("numpy").argmin_grid(fn_np, cluster, params=params)
print(json.dumps({"devices": want, "scan_s": best, "match": res == res_np,
                  "configs": int(cluster.grid_size())}))
"""


def sharded_table(quick: bool = False) -> Tuple[List[Row], dict]:
    """Scaled-grid scan rate vs simulated device count (1/2/4/8): each
    count runs in a fresh subprocess (``XLA_FLAGS`` must precede the
    first jax import) so the parent process keeps the host's real device
    view.  Every lane's argmin is checked bit-identical against the
    numpy oracle; wall-clock SCALING additionally needs as many real
    cores as simulated devices — on fewer, the shards time-slice one CPU
    and the ratio is recorded (and main() only notes it), not gated.

    The lanes are CPU-only.  On a TPU host this process already holds the
    chip, so no child is started at all: the real multi-chip check is
    ``chip_smoke.py --chips 4``."""
    rows: List[Row] = []
    out: dict = {}
    from repro.core.planning_backend import have_backend
    if not have_backend("jax"):
        return rows, out
    import jax
    if jax.default_backend() == "tpu":
        return rows, out
    src = str(Path(__file__).resolve().parent.parent / "src")
    device_counts = (1, 2) if quick else (1, 2, 4, 8)
    repeats = 2 if quick else REPEATS
    out["host_cpus"] = os.cpu_count() or 1
    out["device_counts"] = list(device_counts)
    for d in device_counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("REPRO_PLAN_DEVICES", None)        # the cap under test
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_DRIVER, str(d),
             "1" if quick else "0", str(repeats),
             str(OPERATOR["ss"]), str(OPERATOR["ls"])],
            env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rep = json.loads(proc.stdout.strip().splitlines()[-1])
        out[f"d{d}"] = rep
        rows += [
            (f"resplan.sharded.d{d}_scan_s", rep["scan_s"],
             f"{rep['configs']:,}-point jax sharded scan, {d} simulated "
             "device(s)"),
            (f"resplan.sharded.d{d}_mcfg_per_s",
             rep["configs"] / rep["scan_s"] / 1e6,
             "scan rate, millions of configs per second"),
        ]
    parity = float(all(out[f"d{d}"]["match"] for d in device_counts))
    out["parity_ok"] = parity
    rows.append(("resplan.sharded.parity_ok", parity,
                 "sharded argmin == numpy oracle at every device count "
                 "(1 = agree)"))
    lo, hi = device_counts[0], device_counts[-1]
    scaling = out[f"d{lo}"]["scan_s"] / out[f"d{hi}"]["scan_s"]
    out[f"scaling_{lo}to{hi}_x"] = scaling
    rows.append((f"resplan.sharded.scaling_{lo}to{hi}_x", scaling,
                 f"{lo}-device / {hi}-device scan wall-clock (> 1 needs "
                 f">= {hi} real cores; this host has {out['host_cpus']})"))
    return rows, out


# ----- double-buffered broker flushes (overlap benchmark) ------------------- #

def _plan_sig(p):
    """Structural plan signature (impl/resources/costs, recursively)."""
    if p is None:
        return None
    if p.is_leaf:
        return tuple(sorted(p.tables))
    return (p.impl, p.resources, p.op_cost, p.total_cost,
            _plan_sig(p.left), _plan_sig(p.right))


def overlap_table(quick: bool = False) -> Tuple[List[Row], dict]:
    """Double-buffered vs serial broker flushes on the 8-query Selinger
    workload (5-table queries -> 4 joins each = 32 plan operators): the
    pipelined driver enumerates join level L+1 against stand-in
    cardinalities while wave L's stacked programs execute, so with
    ``double_buffer=True`` the flush syncs only at commit.  Plans must be
    bit-identical either way (asserted by main()); the wall-clock win
    needs a real core for XLA to run on while Python enumerates, so on a
    single-core host the speedup is reported, not gated."""
    rows: List[Row] = []
    out: dict = {}
    be = "jax" if "jax" in _backends() else "numpy"
    schema = random_schema(10, seed=0)
    n_q = 4 if quick else 8
    queries = [random_query(schema, 5, seed=q) for q in range(n_q)]
    cluster = scaled_cluster(1_000, 20) if quick \
        else scaled_cluster(100_000, 100)
    out.update({"backend": be, "queries": n_q,
                "operators": 4 * n_q, "configs": cluster.grid_size(),
                "host_cpus": os.cpu_count() or 1})
    shared_fns: dict = {}             # compiled programs shared, as RAQO does
    sigs, times, geom = {}, {}, {}
    repeats = 1 if quick else 3
    for label, dbl in (("serial", False), ("async", True)):
        best = math.inf
        plans: list = []
        for _ in range(repeats + 1):  # first repeat pays jit compile
            broker = PlanBroker(backend=be, double_buffer=dbl)
            costing = OperatorCosting(models=simulator_cost_models(),
                                      cluster=cluster,
                                      resource_planning="batched",
                                      broker=broker,
                                      _grid_fn_cache=shared_fns)
            t0 = time.perf_counter()
            plans = [selinger_plan(schema, q, costing) for q in queries]
            best = min(best, time.perf_counter() - t0)
            geom[label] = broker.counters_snapshot()
        sigs[label] = [_plan_sig(p) for p in plans]
        times[label] = best
    out["async_waves"] = geom["async"]["waves"]
    out["async_mean_wave"] = geom["async"]["mean_wave"]
    out["serial_s"], out["async_s"] = times["serial"], times["async"]
    out["speedup_x"] = times["serial"] / times["async"]
    out["identical"] = float(sigs["async"] == sigs["serial"])
    rows += [
        ("resplan.overlap.serial_s", out["serial_s"],
         f"{n_q}-query Selinger batch, serial broker flushes ({be})"),
        ("resplan.overlap.async_s", out["async_s"],
         f"{n_q}-query Selinger batch, double-buffered flush waves ({be})"),
        ("resplan.overlap.speedup_x", out["speedup_x"],
         "serial / double-buffered wall-clock (> 1 needs a spare real "
         f"core; this host has {out['host_cpus']})"),
        ("resplan.overlap.identical", out["identical"],
         "double-buffered plans == serial plans (1 = identical)"),
        ("resplan.overlap.async_waves", float(out["async_waves"]),
         "flush waves across the per-query batch (double-buffered)"),
        ("resplan.overlap.async_mean_wave", out["async_mean_wave"],
         "broker requests per double-buffered wave"),
    ]
    return rows, out


# ----- lockstep cross-query Selinger (one wave per DP level) ---------------- #

def lockstep_table(quick: bool = False) -> Tuple[List[Row], dict]:
    """Lockstep cross-query planning (``RAQO.plan_queries`` default) vs
    the per-query double-buffered pipeline (``lockstep=False``) on the
    8-query / 32-operator Selinger workload: every in-flight query's DP
    level L is queued before ONE shared flush, so each wave is a single
    stacked (sum Q_L, P) program per (cost-fn, grid) group instead of Q
    small ones.  A second, 64-query recurring workload (8 templates x 8
    arrivals, the paper's §V recurring-job story) stresses the broker
    memo + base-candidate fan-out at batch width.  Plans must be
    bit-identical either way (asserted by main()); the wall-clock win is
    gated only on multi-core hosts (dispatch overlap needs spare cores)."""
    rows: List[Row] = []
    out: dict = {}
    be = "jax" if "jax" in _backends() else "numpy"
    schema = random_schema(10, seed=0)
    n_q = 4 if quick else 8
    queries = [random_query(schema, 5, seed=q) for q in range(n_q)]
    cluster = scaled_cluster(1_000, 20) if quick \
        else scaled_cluster(100_000, 100)
    out.update({"backend": be, "queries": n_q, "operators": 4 * n_q,
                "configs": cluster.grid_size(),
                "host_cpus": os.cpu_count() or 1})
    raqo = RAQO(schema, cluster=cluster, resource_planning="batched",
                backend=be)                 # shared compiled-program caches
    repeats = 1 if quick else 3
    sigs, times, geom = {}, {}, {}
    for label, lockstep in (("per_query", False), ("lockstep", True)):
        best = math.inf
        plans: list = []
        for _ in range(repeats + 1):        # first repeat pays jit compile
            raqo.broker = PlanBroker(backend=be)    # fresh memo + counters
            t0 = time.perf_counter()
            plans = raqo.plan_queries(queries, lockstep=lockstep)
            best = min(best, time.perf_counter() - t0)
        sigs[label] = [_plan_sig(jp.plan) for jp in plans]
        times[label] = best
        geom[label] = raqo.broker.counters_snapshot()
    out["per_query_s"], out["lockstep_s"] = \
        times["per_query"], times["lockstep"]
    out["speedup_x"] = times["per_query"] / times["lockstep"]
    out["identical"] = float(sigs["lockstep"] == sigs["per_query"])
    out.update({"waves": geom["lockstep"]["waves"],
                "mean_wave": geom["lockstep"]["mean_wave"],
                "max_wave": geom["lockstep"]["max_wave"],
                "per_query_waves": geom["per_query"]["waves"]})
    # recurring batch: lockstep stacks 64 queries' levels into the same
    # handful of waves; the per-query baseline pays 64 wave trains
    n_r = 16 if quick else 64
    recurring = [random_query(schema, 4, seed=q % 8) for q in range(n_r)]
    rec: dict = {}
    for label, lockstep in (("per_query", False), ("lockstep", True)):
        raqo.broker = PlanBroker(backend=be)
        t0 = time.perf_counter()
        raqo.plan_queries(recurring, lockstep=lockstep)
        rec[label] = time.perf_counter() - t0
    out["recurring_queries"] = n_r
    out["recurring_per_query_s"] = rec["per_query"]
    out["recurring_lockstep_s"] = rec["lockstep"]
    out["recurring_speedup_x"] = rec["per_query"] / rec["lockstep"]
    rows += [
        ("resplan.lockstep.per_query_s", out["per_query_s"],
         f"{n_q}-query Selinger batch, per-query pipelined waves ({be})"),
        ("resplan.lockstep.lockstep_s", out["lockstep_s"],
         f"{n_q}-query batch, one wave per DP level across queries ({be})"),
        ("resplan.lockstep.speedup_x", out["speedup_x"],
         "per-query / lockstep wall-clock (gated >= 1.5x on multi-core "
         f"hosts; this host has {out['host_cpus']})"),
        ("resplan.lockstep.identical", out["identical"],
         "lockstep plans == per-query plans (1 = identical)"),
        ("resplan.lockstep.waves", float(out["waves"]),
         f"lockstep flush waves (per-query: {out['per_query_waves']})"),
        ("resplan.lockstep.mean_wave", out["mean_wave"],
         "broker requests per lockstep wave"),
        ("resplan.lockstep.max_wave", float(out["max_wave"]),
         "widest stacked wave (requests)"),
        ("resplan.lockstep.recurring_speedup_x", out["recurring_speedup_x"],
         f"{n_r} recurring queries (8 templates), per-query / lockstep"),
    ]
    return rows, out


def run(quick: bool = False) -> List[Row]:
    """Harness entry: measures and records, never asserts on wall-clock
    (a loaded host must not abort the whole benchmarks/run.py sweep); the
    acceptance thresholds are enforced by main() when run standalone."""
    rows1, tab = overhead_table()
    rows2, scale = scalability(quick)
    rows3, backends = backend_table(quick)
    rows5, pallas = pallas_table(quick, backends)
    rows4, mq = multi_query(quick)
    rows6, shard = sharded_table(quick)
    rows7, overlap = overlap_table(quick)
    rows8, lock = lockstep_table(quick)
    if quick:
        # CI smoke: shrunken grids must not overwrite the tracked JSON or
        # pollute the cross-PR history trend with incomparable numbers
        return rows1 + rows2 + rows3 + rows5 + rows4 + rows6 + rows7 + rows8
    out = Path(__file__).resolve().parent.parent / \
        "BENCH_resource_planning.json"
    payload = {"operator": OPERATOR, "paper_cluster_100x10": tab,
               "scaled_cluster_100000x100": scale, "backends": backends,
               "pallas": pallas, "multi_query": mq, "sharded": shard,
               "overlap": overlap, "lockstep": lock}
    # append this run's summary to the cross-PR trajectory (--report mode
    # of benchmarks/run.py renders the trend)
    history = []
    if out.exists():
        try:
            history = json.loads(out.read_text()).get("history", [])
        except (json.JSONDecodeError, AttributeError):
            history = []
    snapshot = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "batched_speedup_x": tab["batched_speedup_x"],
        "scaled_batched_s": scale["batched_s"],
        "scaled_configs": scale["configs"],
    }
    for be in ("numpy", "jax", "pallas"):
        if be in backends:
            snapshot[f"{be}_scaled_scan_s"] = backends[be]["scaled_scan_s"]
            snapshot[f"{be}_ensemble_us"] = backends[be]["ensemble_us"]
        if be in mq:
            snapshot[f"mq_{be}_broker_s"] = mq[be]["broker_s"]
            snapshot[f"mq_{be}_speedup_x"] = mq[be]["speedup_x"]
    for k in ("vs_jax_scan_x", "many_vs_jax_x", "pallas_many_s"):
        if k in pallas:
            snapshot[f"pallas_{k}" if not k.startswith("pallas") else k] = \
                pallas[k]
    for d in shard.get("device_counts", []):
        snapshot[f"sharded_d{d}_scan_s"] = shard[f"d{d}"]["scan_s"]
    for k in ("parity_ok", "scaling_1to8_x"):
        if k in shard:
            snapshot[f"sharded_{k}"] = shard[k]
    if overlap:
        snapshot["mq_overlap_serial_s"] = overlap["serial_s"]
        snapshot["mq_overlap_async_s"] = overlap["async_s"]
        snapshot["mq_overlap_speedup_x"] = overlap["speedup_x"]
    if lock:
        snapshot["lockstep_8q_s"] = lock["lockstep_s"]
        snapshot["lockstep_per_query_8q_s"] = lock["per_query_s"]
        snapshot["lockstep_speedup_8q_x"] = lock["speedup_x"]
        snapshot["lockstep_identical"] = lock["identical"]
        snapshot["lockstep_64q_s"] = lock["recurring_lockstep_s"]
        snapshot["lockstep_speedup_64q_x"] = lock["recurring_speedup_x"]
        snapshot["lockstep_waves"] = lock["waves"]
        snapshot["lockstep_mean_wave"] = lock["mean_wave"]
        snapshot["lockstep_max_wave"] = lock["max_wave"]
    payload["history"] = history + [snapshot]
    out.write_text(json.dumps(payload, indent=1) + "\n")
    return rows1 + rows2 + rows3 + rows5 + rows4 + rows6 + rows7 + rows8


def main() -> None:
    quick = "--quick" in sys.argv[1:]
    # --no-gate: full grids + tracked-JSON/history write, but no
    # wall-clock acceptance asserts — for shared/loaded runners (the
    # bench-history CI job) where a slow host must not lose the snapshot
    gate = "--no-gate" not in sys.argv[1:]
    enable_compile_cache()
    print("name,value,derived")
    rows = run(quick)
    by_name = {name: value for name, value, _ in rows}
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    if quick or not gate:
        return                      # correctness asserts only
    speedup = by_name["resplan.paper_cluster.batched_speedup_x"]
    scaled_s = by_name["resplan.scaled_100kx100.batched_s"]
    assert speedup >= 10.0, \
        f"batched backend must be >= 10x faster than scalar, got {speedup:.1f}x"
    assert scaled_s < 5.0, \
        f"scaled-cluster batched plan took {scaled_s:.2f}s (>= 5s)"
    if "resplan.backend.scaled_jax_vs_numpy_x" in by_name:
        jx = by_name["resplan.backend.scaled_jax_vs_numpy_x"]
        ex = by_name["resplan.backend.ensemble_vs_2start_x"]
        if by_name["resplan.backend.argmin_match"] != 1.0:
            # float32 near-ties can legitimately break differently (the
            # planners re-commit winners through float64); report loudly
            # but do not fail the gate on it
            print("WARNING: jax and numpy argmins diverged (fp near-tie)")
        assert jx >= 1.0, \
            f"jax scaled-grid scan must at least match numpy, got {jx:.2f}x"
        assert ex >= 2.0, \
            f"ensemble climb must beat the 2-start climb >= 2x, got {ex:.2f}x"
    if "resplan.pallas.vs_jax_scan_x" in by_name:
        px = by_name["resplan.pallas.vs_jax_scan_x"]
        assert px >= 1.0, \
            f"fused pallas scan must at least match the jitted jax scan " \
            f"on the 10M-point grid, got {px:.2f}x"
        if by_name["resplan.pallas.argmin_match"] != 1.0:
            print("WARNING: pallas and numpy argmins diverged "
                  "(fp near-tie)")
        if by_name.get("resplan.multi_query.pallas.broker_match",
                       1.0) != 1.0:
            print("WARNING: pallas broker and per-operator argmins "
                  "diverged")
    ident = by_name["resplan.multi_query.numpy.identical"]
    assert ident == 1.0, \
        "numpy broker must be bit-identical with the per-operator loop"
    if "resplan.multi_query.jax.speedup_x" in by_name:
        bx = by_name["resplan.multi_query.jax.speedup_x"]
        assert bx >= 3.0, \
            f"jax broker must be >= 3x per-operator jax planning, got {bx:.2f}x"
    # sharded + overlap: bit-identity is unconditional; the wall-clock
    # wins need real parallel cores (simulated devices time-slice one
    # CPU), so those are gated only where the host can express them
    cpus = os.cpu_count() or 1
    if "resplan.sharded.parity_ok" in by_name:
        assert by_name["resplan.sharded.parity_ok"] == 1.0, \
            "sharded scan argmin diverged from the numpy oracle"
        sx = by_name.get("resplan.sharded.scaling_1to8_x")
        if sx is not None:
            if cpus >= 8:
                assert sx >= 1.0, \
                    f"8-device sharded scan slower than 1-device " \
                    f"({sx:.2f}x) on an {cpus}-core host"
            elif sx < 1.0:
                print(f"NOTE: 1->8 device scaling {sx:.2f}x on a "
                      f"{cpus}-core host (simulated devices time-slice)")
    if "resplan.overlap.identical" in by_name:
        assert by_name["resplan.overlap.identical"] == 1.0, \
            "double-buffered broker plans diverged from serial flushes"
        ox = by_name["resplan.overlap.speedup_x"]
        if ox < 1.0:
            print(f"NOTE: double-buffered flush speedup {ox:.2f}x "
                  f"({cpus}-core host; overlap needs a spare core)")
    if "resplan.lockstep.identical" in by_name:
        assert by_name["resplan.lockstep.identical"] == 1.0, \
            "lockstep plans diverged from the per-query pipeline"
        lx = by_name["resplan.lockstep.speedup_x"]
        if cpus >= 4:
            assert lx >= 1.5, \
                f"lockstep must be >= 1.5x the per-query pipeline on a " \
                f"{cpus}-core host, got {lx:.2f}x"
        elif lx < 1.5:
            print(f"NOTE: lockstep speedup {lx:.2f}x ({cpus}-core host; "
                  "stacked waves need spare cores to win)")


if __name__ == "__main__":
    main()
