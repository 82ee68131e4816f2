"""Streaming planner service benchmark (live arrivals, one broker).

Every other bench in this repo hands the broker a *static* batch; this
one measures the repo's first throughput and tail-latency numbers: a
``StreamingPlannerService`` (repro.service) planning a continuous
closed-loop query stream — finished tenant slots are refilled the moment
they free, keeping ``concurrency`` queries in flight on ONE session
broker — plus an open-loop section replaying a Poisson arrival trace
against the wall clock, where queueing delay shows up in the
submit->resolve latency rather than in a lost arrival.

Sections (``name,value,derived`` CSV rows like every bench here):

    streaming.identity.<backend>   admission-join == solo planning (1.0)
    streaming.smoke.<backend>.*    short closed loop (the CI-gated p99)
    streaming.closed.<backend>.*   full closed loop, >= 256 tenants
    streaming.open.<backend>.*     open-loop Poisson replay
    streaming.traced.*             traced run: request histogram +
                                   critical-path split + trace artifacts

The *smoke* section runs the identical configuration in quick and full
modes, so the snapshot a full run appends to the tracked
BENCH_streaming.json carries a like-for-like baseline for CI: the
``streaming`` CI lane runs ``--quick`` and ``main()`` fails when the
fresh smoke p99 exceeds 2x the last tracked snapshot's (the
latency-regression gate; conditioned on ``os.cpu_count()`` like every
wall-clock gate, while the identity gate is unconditional).  Quick runs
never touch the tracked JSON.  The measured loops run after a warmup
pass on the same RAQO/broker (steady state: compiled search programs
and session memo warm), which is the regime a long-lived service
actually operates in.

    PYTHONPATH=src python -m benchmarks.streaming_bench
    PYTHONPATH=src python -m benchmarks.streaming_bench --quick
    PYTHONPATH=src python -m benchmarks.streaming_bench --no-gate
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from typing import List, Optional, Tuple

from repro.core.cluster import paper_cluster
from repro.core.plan_broker import PlanBroker
from repro.core.raqo import RAQO
from repro.core.schema import random_query, random_schema
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import get_metrics, get_tracer, write_chrome_trace
from repro.service import StreamingPlannerService, poisson_trace

Row = Tuple[str, float, str]

ROOT = Path(__file__).resolve().parent.parent

SCHEMA_TABLES = 16
SMOKE = {"concurrency": 16, "n_queries": 64}    # CI-gated configuration
FULL = {"concurrency": 256, "n_queries": 512}   # the >= 256-tenant story
OPEN = {"rate": 100.0, "n": 200}                # open-loop Poisson replay


def _backends() -> List[str]:
    out = ["numpy"]
    try:
        import jax  # noqa: F401
        out.append("jax")
    except ImportError:
        pass
    return out


def _mk_raqo(schema, backend: str) -> RAQO:
    return RAQO(schema=schema, cluster=paper_cluster(24, 8),
                resource_planning="batched", backend=backend,
                broker=PlanBroker(backend=backend))


def _workload(schema, n: int, seed: int) -> List[Tuple[int, Tuple[str, ...]]]:
    trace = poisson_trace(schema, n, rate=1000.0, seed=seed, tenants=64)
    return [(a.tenant, a.tables) for a in trace]


def _tree_sig(n) -> Optional[tuple]:
    if n is None:
        return None
    if n.is_leaf:
        return (tuple(sorted(n.tables)),)
    return (tuple(sorted(n.tables)), n.impl, tuple(n.resources),
            n.total_cost, _tree_sig(n.left), _tree_sig(n.right))


def _identity(schema, backend: str) -> float:
    """Plan a churning stream (staggered admissions joining incumbents
    mid-run) and compare every ticket's plan against planning the same
    query SOLO on a fresh broker.  Returns 1.0 on bit-identity."""
    svc = StreamingPlannerService(_mk_raqo(schema, backend))
    queries = [random_query(schema, 2 + (i % 5), seed=100 + i)
               for i in range(12)]
    tickets = []
    for i, q in enumerate(queries):
        tickets.append(svc.submit(q, tenant=i))
        if i % 2:
            svc.step()              # admissions interleave with waves
    svc.drain()
    for t in tickets:
        solo = _mk_raqo(schema, backend).joint(t.tables)
        if _tree_sig(solo.plan) != _tree_sig(t.joint.plan):
            return 0.0
    return 1.0


def _closed_loop(schema, backend: str, concurrency: int, n_queries: int,
                 seed: int) -> dict:
    """One warmed closed-loop measurement on a fresh RAQO/broker."""
    raqo = _mk_raqo(schema, backend)
    warm = StreamingPlannerService(raqo)
    warm.run_closed_loop(_workload(schema, max(8, n_queries // 8),
                                   seed=seed + 999), concurrency)
    svc = StreamingPlannerService(raqo)     # same broker, same programs
    work = _workload(schema, n_queries, seed=seed)
    t0 = time.perf_counter()
    svc.run_closed_loop(work, concurrency)
    elapsed = time.perf_counter() - t0
    rep = svc.report(elapsed_s=elapsed)
    rep["concurrency"] = concurrency
    return rep


def _open_loop(schema, backend: str, rate: float, n: int) -> dict:
    raqo = _mk_raqo(schema, backend)
    warm = StreamingPlannerService(raqo)
    warm.run_closed_loop(_workload(schema, 16, seed=1234), 8)
    svc = StreamingPlannerService(raqo)
    trace = poisson_trace(schema, n, rate=rate, seed=11, tenants=64)
    t0 = time.perf_counter()
    svc.run_open_loop(trace)
    elapsed = time.perf_counter() - t0
    return svc.report(elapsed_s=elapsed)


def _traced(schema, backend: str) -> dict:
    """Short traced closed loop: request histogram, critical-path split,
    and the Perfetto trace artifact for upload."""
    tr, mx = get_tracer(), get_metrics()
    was = tr.enabled
    tr.reset()
    mx.reset()
    tr.enable()
    try:
        svc = StreamingPlannerService(_mk_raqo(schema, backend))
        t0 = time.perf_counter()
        svc.run_closed_loop(_workload(schema, 48, seed=77), 16)
        rep = svc.report(elapsed_s=time.perf_counter() - t0)
        art = ROOT / "artifacts"
        art.mkdir(exist_ok=True)
        write_chrome_trace(art / "trace_streaming.json", tr)
        return rep
    finally:
        tr.enabled = was
        tr.reset()
        mx.reset()


def _rep_rows(prefix: str, rep: dict, what: str) -> List[Row]:
    rows = [(f"{prefix}.plans_per_s", rep.get("plans_per_s", 0.0),
             f"steady-state planning throughput ({what})"),
            (f"{prefix}.p50_s", rep.get("query_p50_s") or 0.0,
             "submit->resolve latency p50"),
            (f"{prefix}.p99_s", rep.get("query_p99_s") or 0.0,
             "submit->resolve latency p99"),
            (f"{prefix}.completed", float(rep["completed"]),
             f"queries planned over {rep['waves']} waves"),
            (f"{prefix}.mean_wave", rep["broker"]["mean_wave"],
             "requests per flush wave (stacking width)")]
    if "concurrency" in rep:
        rows.append((f"{prefix}.concurrency", float(rep["concurrency"]),
                     "concurrent tenant sessions on one broker"))
    return rows


def run(quick: bool = False) -> List[Row]:
    schema = random_schema(SCHEMA_TABLES, seed=0)
    rows: List[Row] = []
    summary: dict = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    backends = _backends()
    for be in backends:
        rows.append((f"streaming.identity.{be}", _identity(schema, be),
                     "admission-join plans bit-identical to solo (1=ok)"))
        smoke = _closed_loop(schema, be, SMOKE["concurrency"],
                             SMOKE["n_queries"], seed=42)
        rows += _rep_rows(f"streaming.smoke.{be}", smoke,
                          f"closed loop x{SMOKE['concurrency']}, {be}")
        summary[f"smoke_{be}_p50_s"] = smoke.get("query_p50_s")
        summary[f"smoke_{be}_p99_s"] = smoke.get("query_p99_s")
        summary[f"smoke_{be}_plans_per_s"] = smoke.get("plans_per_s")
    if not quick:
        for be in backends:
            full = _closed_loop(schema, be, FULL["concurrency"],
                                FULL["n_queries"], seed=43)
            rows += _rep_rows(f"streaming.closed.{be}", full,
                              f"closed loop x{FULL['concurrency']}, {be}")
            summary[f"closed_{be}_plans_per_s"] = full.get("plans_per_s")
            summary[f"closed_{be}_p50_s"] = full.get("query_p50_s")
            summary[f"closed_{be}_p99_s"] = full.get("query_p99_s")
            summary[f"closed_{be}_mean_wave"] = full["broker"]["mean_wave"]
            summary["closed_concurrency"] = full["concurrency"]
        be = backends[-1]
        op = _open_loop(schema, be, OPEN["rate"], OPEN["n"])
        rows += _rep_rows(f"streaming.open.{be}", op,
                          f"poisson {OPEN['rate']}/s replay, {be}")
        summary[f"open_{be}_p99_s"] = op.get("query_p99_s")
        traced = _traced(schema, be)
        req = traced.get("request", {})
        cp = traced.get("critical_path", {})
        rows += [("streaming.traced.request_p99_s", req.get("p99_s", 0.0),
                  f"broker.request_s p99 over {req.get('count', 0)} "
                  "requests (traced run)"),
                 ("streaming.traced.cp_queue_s", cp.get("mean_queue_s",
                                                        0.0),
                  "mean critical-path queue (submit->dispatch)"),
                 ("streaming.traced.cp_execute_s", cp.get("mean_execute_s",
                                                          0.0),
                  "mean critical-path execute (dispatch->sync)"),
                 ("streaming.traced.cp_commit_s", cp.get("mean_commit_s",
                                                         0.0),
                  "mean critical-path commit (sync->resolve)")]
        summary["traced_request_p99_s"] = req.get("p99_s")
        summary["traced_requests"] = req.get("count")

    art = ROOT / "artifacts"
    art.mkdir(exist_ok=True)
    (art / "streaming_summary.json").write_text(
        json.dumps(dict(summary, backends=backends, quick=quick),
                   indent=1) + "\n")
    if not quick:
        _append_history(summary)
    return rows


def _append_history(snapshot: dict) -> None:
    """Append this run's snapshot to the tracked BENCH_streaming.json
    (cross-PR trend convention shared with the other BENCH_*.json)."""
    out = ROOT / "BENCH_streaming.json"
    history = []
    if out.exists():
        try:
            history = json.loads(out.read_text()).get("history", [])
        except (json.JSONDecodeError, AttributeError):
            history = []
    history.append(snapshot)
    out.write_text(json.dumps(
        {"description": "streaming planner service under live arrivals "
                        "(streaming_bench)",
         "latest": snapshot, "history": history}, indent=1) + "\n")


def _gate_p99(by_name: dict) -> None:
    """CI latency-regression gate: the fresh smoke p99 must stay within
    2x of the last tracked snapshot's.  Gated on the numpy backend —
    deterministic dispatch, no JIT-compile variance — and skipped when
    there is no tracked history yet."""
    tracked = ROOT / "BENCH_streaming.json"
    if not tracked.exists():
        print("streaming.gate: no tracked BENCH_streaming.json, skipping")
        return
    try:
        last = json.loads(tracked.read_text())["history"][-1]
    except (json.JSONDecodeError, KeyError, IndexError):
        print("streaming.gate: unreadable tracked history, skipping")
        return
    prev = last.get("smoke_numpy_p99_s")
    cur = by_name.get("streaming.smoke.numpy.p99_s")
    if not prev or not cur:
        print("streaming.gate: missing smoke p99, skipping")
        return
    assert cur <= 2.0 * prev, \
        f"streaming smoke p99 regressed >2x: {cur:.4f}s vs tracked " \
        f"{prev:.4f}s (see BENCH_streaming.json)"
    print(f"streaming.gate: smoke p99 {cur:.4f}s vs tracked {prev:.4f}s "
          f"({cur / prev:.2f}x) within 2x")


def main() -> None:
    quick = "--quick" in sys.argv[1:]
    gate = "--no-gate" not in sys.argv[1:]
    enable_compile_cache()
    print("name,value,derived")
    rows = run(quick)
    by_name = {name: value for name, value, _ in rows}
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    # identity is unconditional — fp or ordering divergence is a bug
    for be in _backends():
        assert by_name[f"streaming.identity.{be}"] == 1.0, \
            f"admission-join plans diverged from solo planning on {be}"
    cpus = os.cpu_count() or 1
    if gate and cpus >= 4:
        _gate_p99(by_name)
    elif gate:
        print(f"streaming.gate: {cpus} cpus < 4, wall-clock gate skipped")
    if quick or not gate:
        return
    # full-mode structural gates (the acceptance criteria)
    conc = by_name.get("streaming.closed.numpy.concurrency", 0.0)
    assert conc >= 256, \
        f"closed-loop section must run >= 256 tenant sessions, got {conc}"
    pps = by_name.get("streaming.closed.numpy.plans_per_s", 0.0)
    assert pps > 0, "closed-loop section reported zero throughput"


if __name__ == "__main__":
    main()
