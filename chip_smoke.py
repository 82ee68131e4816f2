"""Smoke run of the served RAQO planner on a TPU chip.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # the sharded scan on a 4-chip host

Drives ``StreamingPlannerService`` -> ``LockstepDriver`` -> ``PlanBroker``
-> plan backend, with the ``jax`` backend compiled for the chip, in one
process (a process that has touched JAX holds the chip).

Phase A serves ``FULL`` (256 concurrent tenants, 512 queries of 2-6
tables from ``random_schema(16, seed=0)``) on the paper's §VII grid
``paper_cluster(100, 10)`` and compares every ticket's plan with the
same stream planned on the float64 ``numpy`` backend.  Phase B serves
the same stream on §VII-C's ``scaled_cluster(100_000, 100)`` (10M
configurations) and re-solves a sample of the flushed requests on
``numpy``.  ``--chips 4`` instead runs Phase B's single-request scan and
stacked flush on a 4-device plan mesh and compares them with the same
backend on one device and with the ``numpy`` sample.

"Agree" means the same plan, or, where the f32 device broke a near-tie
differently, a plan whose float64 cost is within ``REL_TOL`` of the
oracle's.  Any other difference, a platform other than ``tpu`` or any
exception exits nonzero without the final line.  The
timings printed on the way are set-up observations, not benchmark
numbers.  The last line of stdout is the JSON verdict.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
REL_TOL = 1e-6            # float64 cost tolerance for a differing plan
N_RESOLVE = 16            # flushed requests re-solved on numpy
FULL = {"concurrency": 256, "n_queries": 512}   # the 256-tenant stream
SCHEMA_TABLES = 16        # random_schema(SCHEMA_TABLES, seed=0)
WARMUP_QUERIES = 64       # warm-up prefix: FULL n_queries // 8
FLUSH_Q = 64              # stacked-flush width of the four-chip phase
WORKLOAD_SEED = 43        # the stream's arrival seed


class Recorder:
    """The backend under test, unchanged, with every stacked grid scan it
    finalizes recorded as ``(fn, params, result)`` for the numpy
    re-solve."""

    def __init__(self, inner):
        self._inner = inner
        self.flushed = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def argmin_grid_many_async(self, fn, cluster, params_many, **kw):
        fin = self._inner.argmin_grid_many_async(fn, cluster, params_many,
                                                 **kw)

        def finalize():
            res = fin()
            self.flushed += [(fn, tuple(p), r) for p, r in
                             zip(np.asarray(params_many).tolist(), res)]
            return res
        return finalize


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def _say(tag: str, **fields) -> None:
    print(f"chip_smoke {tag} {json.dumps(fields, sort_keys=True)}",
          flush=True)


def _device(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _workload(schema, n: int, seed: int) -> list:
    """``n`` queries of 64 tenants as ``(tenant, tables)``, in the order
    of a seeded Poisson arrival trace."""
    from repro.service import poisson_trace
    trace = poisson_trace(schema, n, rate=1000.0, seed=seed, tenants=64)
    return [(a.tenant, a.tables) for a in trace]


def _tree_sig(n):
    """A plan tree's tables, join impls, resources and float64 costs."""
    if n is None:
        return None
    if n.is_leaf:
        return (tuple(sorted(n.tables)),)
    return (tuple(sorted(n.tables)), n.impl, tuple(n.resources),
            n.total_cost, _tree_sig(n.left), _tree_sig(n.right))


def _backend(devices: int):
    from repro.core.planning_backend import JaxPlanBackend
    be = JaxPlanBackend(devices=devices)
    _require(be.device_count() == devices,
             f"jax plans on {be.device_count()} device(s), not {devices}")
    return be


def _raqo(schema, cluster, backend):
    from repro.core.cost_model import simulator_cost_models
    from repro.core.plan_broker import PlanBroker
    from repro.core.raqo import RAQO
    return RAQO(schema=schema, cluster=cluster,
                models=simulator_cost_models(), resource_planning="batched",
                backend=backend, broker=PlanBroker(backend=backend))


def _serve(raqo, work, concurrency: int, warm: bool) -> tuple:
    """Serve ``work`` closed-loop, after a warm-up pass on the same broker
    when ``warm``; -> (tickets, report)."""
    from repro.obs import get_metrics, get_tracer
    from repro.service import StreamingPlannerService
    warm_s = 0.0
    if warm:
        t0 = time.perf_counter()
        StreamingPlannerService(raqo).run_closed_loop(
            work[:WARMUP_QUERIES], concurrency)
        warm_s = time.perf_counter() - t0
    tr, mx = get_tracer(), get_metrics()
    tr.reset()
    mx.reset()
    tr.enable()                   # the report's request split and spans
    try:
        svc = StreamingPlannerService(raqo)
        t0 = time.perf_counter()
        tickets = svc.run_closed_loop(work, concurrency)
        rep = svc.report(elapsed_s=time.perf_counter() - t0)
        rep["programs_built"] = mx.counter("backend.programs_built").value
    finally:
        tr.disable()
        tr.reset()
        mx.reset()
    rep["warmup_s"] = warm_s
    rep["researches"] = raqo.broker.stats.broker_researches
    return tickets, rep


def _report_run(phase: str, name: str, rep: dict, dev: dict) -> None:
    _say(f"phase{phase}.{name}", **dev, plans_per_s=rep["plans_per_s"],
         p50_s=rep["query_p50_s"], p99_s=rep["query_p99_s"],
         waves=rep["waves"], mean_wave=rep["broker"]["mean_wave"],
         programs_built=rep["programs_built"], warmup_s=rep["warmup_s"],
         numpy_researches=rep["researches"])


class Tally:
    """Plans that differ from a reference, and the largest relative
    float64 cost gap among them; a gap beyond ``REL_TOL`` raises."""

    def __init__(self):
        self.differ, self.max_gap = 0, 0.0

    def check(self, what: str, same: bool, cost: float,
              ref_cost: float) -> None:
        if same:
            return
        if math.isinf(cost) or math.isinf(ref_cost) or ref_cost == 0:
            gap = 0.0 if cost == ref_cost else math.inf
        else:
            gap = abs(cost - ref_cost) / abs(ref_cost)
        if not gap <= REL_TOL:
            raise AssertionError(f"{what}: plan costs {cost}, the "
                                 f"reference's {ref_cost}")
        self.differ += 1
        self.max_gap = max(self.max_gap, gap)

    def fields(self) -> dict:
        return {"differing_plans": self.differ, "max_rel_gap": self.max_gap}


def _compare_tickets(got, ref) -> Tally:
    """Every ticket's plan against ``ref``'s, by their float64 costs."""
    _require(len(got) == len(ref), f"{len(got)} tickets, {len(ref)} in ref")
    tally = Tally()
    for g, r in zip(got, ref):
        _require(g.tables == r.tables, f"ticket {g.tables} vs {r.tables}")
        gp, rp = g.joint.plan, r.joint.plan
        tally.check(f"plan for {g.tables}", _tree_sig(gp) == _tree_sig(rp),
                    gp.total_cost, rp.total_cost)
    return tally


def _reference_fns(raqo) -> dict:
    """id(device cost fn) -> (impl, float64 numpy cost fn): the plain
    reference evaluates the cost model directly, not the planner."""
    models = raqo.models
    out = {}
    for (impl, _, _), fn in raqo._grid_fn_shared.items():
        model = models[impl]
        out[id(fn)] = (impl, lambda c, p, m=model:
                       m.cost_grid(p[0], p[1], c, xp=np))
    return out


def _sample(flushed, refs, n: int) -> list:
    """``n`` distinct flushed requests spread evenly over the sorted
    (impl, params) keys, so both join implementations and the whole
    size range are covered: [(impl, params, ref_fn)]."""
    reqs = {}
    for fn, p, _ in flushed:
        impl, ref = refs[id(fn)]
        reqs[(impl, p)] = ref
    keys = sorted(reqs)
    _require(len(keys) >= n, f"only {len(keys)} distinct flushed requests "
             f"to re-solve, need {n}")
    return [keys[i] + (reqs[keys[i]],)
            for i in np.linspace(0, len(keys) - 1, n).round().astype(int)]


def _agree(tally: Tally, what: str, got, ref, ref_fn, params) -> None:
    """One request's ``(config, cost)`` answer against ``ref``'s, both
    configs re-costed in float64 by the plain reference ``ref_fn``."""
    if got[0] != ref[0] and (got[0] is None or ref[0] is None):
        raise AssertionError(f"{what}: plan {got} against {ref} for "
                             f"params {params}")
    cost = [math.inf, math.inf] if got[0] == ref[0] else \
        ref_fn(np.asarray([got[0], ref[0]]), np.asarray(params))
    tally.check(f"{what} for params {params}", got[0] == ref[0],
                float(cost[0]), float(cost[1]))


def _resolve_sample(picks, cluster, answers: dict) -> dict:
    """Re-solve each pick on numpy and check every backend's answer."""
    from repro.core.planning_backend import get_backend
    np_be = get_backend("numpy")
    tallies = {name: Tally() for name in answers}
    for impl, params, ref_fn in picks:
        ref = np_be.argmin_grid(ref_fn, cluster, params=np.asarray(params))
        for name, ans in answers.items():
            if (impl, params) in ans:
                _agree(tallies[name], f"{name} {impl} vs numpy",
                       ans[(impl, params)], ref, ref_fn, params)
    return {name: t.fields() for name, t in tallies.items()}


def phase_a(dev, schema, work, concurrency) -> None:
    from repro.core.cluster import paper_cluster
    from repro.core.planning_backend import get_backend
    cluster = paper_cluster(100, 10)
    oracle, _ = _serve(_raqo(schema, cluster, get_backend("numpy")), work,
                       concurrency, warm=False)
    raqo = _raqo(schema, cluster, _backend(1))
    tickets, rep = _serve(raqo, work, concurrency, warm=True)
    _report_run("A", "jax", rep, dev)
    _say("phaseA.jax.vs_numpy", tickets=len(tickets),
         numpy_researches=rep["researches"],
         **_compare_tickets(tickets, oracle).fields())


def phase_b(dev, schema, work, concurrency) -> None:
    from repro.core.cluster import scaled_cluster
    cluster = scaled_cluster(100_000, 100)
    rec = Recorder(_backend(1))
    raqo = _raqo(schema, cluster, rec)
    _, rep = _serve(raqo, work, concurrency, warm=True)
    _report_run("B", "jax", rep, dev)
    refs = _reference_fns(raqo)
    answers = {"jax": {(refs[id(fn)][0], p): r for fn, p, r in rec.flushed}}
    picks = _sample(rec.flushed, refs, N_RESOLVE)
    _say("phaseB.numpy_resolve", requests=len(picks),
         **_resolve_sample(picks, cluster, answers))


def phase_sharded(dev, schema, work, concurrency, chips) -> None:
    """Phase B's scan and stacked flush on a ``chips``-device plan mesh,
    against the same backend on one device and the numpy sample."""
    from repro.core.cluster import paper_cluster, scaled_cluster
    from repro.core.planning_backend import get_backend
    # real request params: the flushed (ss, ls) of the served stream,
    # planned on numpy over the small grid (host-only set-up)
    rec = Recorder(get_backend("numpy"))
    raqo = _raqo(schema, paper_cluster(100, 10), rec)
    _serve(raqo, work[:WARMUP_QUERIES], concurrency, warm=False)
    refs = _reference_fns(raqo)
    picks = _sample(rec.flushed, refs, N_RESOLVE)
    flushes = {}                  # impl -> FLUSH_Q params, picks first
    for impl, p, _ in picks:
        flushes.setdefault(impl, []).append(p)
    for fn, p, _ in rec.flushed:
        ps = flushes.setdefault(refs[id(fn)][0], [])
        if len(ps) < FLUSH_Q and p not in ps:
            ps.append(p)
    ref_fns = dict(refs.values())
    cluster = scaled_cluster(100_000, 100)
    answers = {}
    for n_dev in (chips, 1):
        be = _backend(n_dev)
        costing = _raqo(schema, cluster, be)._costing()
        flush, scan = {}, {}
        t0 = time.perf_counter()
        for impl, ps in sorted(flushes.items()):
            fn = costing._grid_fn(impl, be)
            res = be.argmin_grid_many(fn, cluster, np.asarray(ps))
            flush.update({(impl, p): r for p, r in zip(ps, res)})
            for _, p, _ in (x for x in picks if x[0] == impl):
                scan[(impl, p)] = be.argmin_grid(fn, cluster,
                                                 params=np.asarray(p))
        answers[f"jax@{n_dev}.flush"] = flush
        answers[f"jax@{n_dev}.scan"] = scan
        wall_s = time.perf_counter() - t0
        out_devs = _output_devices(be)
        _say(f"sharded.jax@{n_dev}", **dev, plan_devices=n_dev,
             output_devices=out_devs, wall_s=wall_s)
        _require(out_devs == n_dev, f"jax@{n_dev}: outputs land on "
                 f"{out_devs} device(s)")
    for op in ("flush", "scan"):
        a, b = answers[f"jax@{chips}.{op}"], answers[f"jax@1.{op}"]
        tally = Tally()
        for k in sorted(b):
            _agree(tally, f"jax {op} {chips} vs 1 device", a[k], b[k],
                   ref_fns[k[0]], k[1])
        _say(f"sharded.jax.{op}.{chips}_vs_1", requests=len(b),
             **tally.fields())
    _say("sharded.numpy_resolve", requests=len(picks),
         **_resolve_sample(picks, cluster, answers))


def _output_devices(be) -> int:
    """The most devices any program in ``be``'s memo puts its outputs on,
    from re-running each program on zero params: a program that claims a
    mesh but runs on one device shows 1."""
    most = 0
    for key, (_, prog) in be._programs.items():
        kind, extra = key[0], key[3]
        if kind == "scan_many":       # (chunk, Qpad, P, D)
            out = prog(0, np.zeros(extra[1:3], np.float32))
        elif kind == "scan":          # (chunk, has_params, D)
            out = prog(0, np.zeros(2, np.float32))
        else:
            continue
        most = max(most, len(out[0].sharding.device_set))
    return most


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=0,
                    help="run only the sharded scan on this many chips")
    args = ap.parse_args()

    import jax
    dev = _device(jax)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX runs on {dev['platform']}")
    if args.chips and dev["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{dev['count']} device(s)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.schema import random_schema
    from repro.launch.compile_cache import enable_compile_cache
    _say("setup", compile_cache=enable_compile_cache(), **dev)

    schema = random_schema(SCHEMA_TABLES, seed=0)
    work = _workload(schema, FULL["n_queries"], seed=WORKLOAD_SEED)
    conc = FULL["concurrency"]
    t0 = time.perf_counter()
    if args.chips:
        phase_sharded(dev, schema, work, conc, args.chips)
    else:
        phase_a(dev, schema, work, conc)
        phase_b(dev, schema, work, conc)
    _say("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
