"""Telemetry bench: a traced 8-query lockstep Selinger run through the
observability subsystem (repro.obs), exporting the full artifact set —

    artifacts/trace_lockstep.json      Chrome trace-event JSON (Perfetto)
    artifacts/trace_attribution.md     per-query attribution table
    artifacts/telemetry_summary.json   wave geometry + latency percentiles

and printing the usual ``name,value,derived`` CSV rows.  Full (non
``--quick``) runs also append a snapshot to the tracked
BENCH_telemetry.json ``history`` so request p50/p99 and the wave
assembly/execute/commit split trend across PRs (rendered by
``benchmarks/run.py --report`` under "## telemetry").

The run itself enables the tracer programmatically (the env-var path is
covered by tests/CI), plans the same workload as ``lockstep_table`` in
resource_planning_bench, and asserts the reconciliation contract before
writing anything: wave spans must agree exactly with the broker's
``counters_snapshot()`` and the request histogram must account for every
submitted request — a trace that disagrees with the counters is worse
than no trace.

    PYTHONPATH=src python -m benchmarks.telemetry_bench
    PYTHONPATH=src python -m benchmarks.telemetry_bench --quick
    PYTHONPATH=src python -m benchmarks.run --trace [--quick]
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import List, Tuple

from repro.core.cluster import scaled_cluster
from repro.core.plan_broker import PlanBroker
from repro.core.raqo import RAQO
from repro.core.schema import random_query, random_schema
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import (get_metrics, get_tracer, wave_summary,
                       write_attribution, write_chrome_trace)

Row = Tuple[str, float, str]

ROOT = Path(__file__).resolve().parent.parent


def _backend() -> str:
    try:
        import jax  # noqa: F401
        return "jax"
    except ImportError:
        return "numpy"


def run(quick: bool = False) -> List[Row]:
    """Trace one lockstep batch; write artifacts; return CSV rows."""
    tr, mx = get_tracer(), get_metrics()
    was = tr.enabled
    tr.reset()
    mx.reset()
    tr.enable()
    try:
        be = _backend()
        schema = random_schema(10, seed=0)
        n_q = 4 if quick else 8
        queries = [random_query(schema, 5, seed=q) for q in range(n_q)]
        cluster = scaled_cluster(1_000, 20) if quick \
            else scaled_cluster(100_000, 100)
        broker = PlanBroker(backend=be)
        raqo = RAQO(schema, cluster=cluster, resource_planning="batched",
                    backend=be, broker=broker)
        t0 = time.perf_counter()
        plans = raqo.plan_queries(queries)
        wall_s = time.perf_counter() - t0

        cs = broker.counters_snapshot()
        ws = wave_summary(tr, mx)
        # reconciliation gate: the trace must describe the counted run
        assert ws["waves"] == cs["waves"], (ws["waves"], cs["waves"])
        assert ws["wave_sizes"] == cs["wave_sizes"]
        assert ws["request"]["count"] == cs["requests"]

        art = ROOT / "artifacts"
        write_chrome_trace(art / "trace_lockstep.json", tr)
        write_attribution(art / "trace_attribution.md", plans, tr, mx)
        summary = dict(ws, backend=be, queries=n_q, wall_s=wall_s,
                       requests=cs["requests"],
                       dedup_hits=cs["dedup_hits"])
        art.mkdir(exist_ok=True)
        (art / "telemetry_summary.json").write_text(
            json.dumps(summary, indent=1) + "\n")

        if not quick:
            _append_history(summary)

        req, st1 = ws["request"], ws["wave_stage1"]
        dis, exe, com = ws["wave_dispatch"], ws["wave_execute"], \
            ws["wave_commit"]
        rows: List[Row] = [
            ("telemetry.wall_s", wall_s,
             f"traced {n_q}-query lockstep batch ({be})"),
            ("telemetry.request_p50_s", req.get("p50_s", 0.0),
             f"submit->resolve latency p50 over {req['count']} requests"),
            ("telemetry.request_p99_s", req.get("p99_s", 0.0),
             "submit->resolve latency p99"),
            ("telemetry.wave_stage1_mean_s", st1.get("mean_s", 0.0),
             "dedup+cache fronting per wave"),
            ("telemetry.wave_dispatch_mean_s", dis.get("mean_s", 0.0),
             "program launches per dispatched wave"),
            ("telemetry.wave_execute_mean_s", exe.get("mean_s", 0.0),
             "device execute (host sync) per dispatched wave"),
            ("telemetry.wave_commit_mean_s", com.get("mean_s", 0.0),
             "float64 commit + fan-out per dispatched wave"),
            ("telemetry.waves", float(ws["waves"]),
             f"flush waves (sizes {ws['wave_sizes']})"),
            ("telemetry.programs_built", float(ws["programs_built"]),
             "backend programs compiled during the run"),
            ("telemetry.compiles", float(ws["compiles"]),
             "XLA compiles (and compile-cache loads) during the run"),
            ("telemetry.trace_events", float(len(tr.events())),
             "events in artifacts/trace_lockstep.json"),
        ]
        return rows
    finally:
        tr.enabled = was
        tr.reset()
        mx.reset()


def _append_history(summary: dict) -> None:
    """Append this run's snapshot to the tracked BENCH_telemetry.json
    (same cross-PR trend convention as BENCH_resource_planning.json)."""
    out = ROOT / "BENCH_telemetry.json"
    history = []
    if out.exists():
        try:
            history = json.loads(out.read_text()).get("history", [])
        except (json.JSONDecodeError, AttributeError):
            history = []
    req = summary["request"]
    snapshot = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": summary["backend"],
        "requests": summary["requests"],
        "request_p50_s": req.get("p50_s"),
        "request_p99_s": req.get("p99_s"),
        "wave_stage1_mean_s": summary["wave_stage1"].get("mean_s"),
        "wave_dispatch_mean_s": summary["wave_dispatch"].get("mean_s"),
        "wave_execute_mean_s": summary["wave_execute"].get("mean_s"),
        "wave_commit_mean_s": summary["wave_commit"].get("mean_s"),
        "waves": summary["waves"],
        "max_wave": summary["max_wave"],
        "mean_wave": summary["mean_wave"],
        "programs_built": summary["programs_built"],
        "compiles": summary["compiles"],
    }
    history.append(snapshot)
    out.write_text(json.dumps(
        {"description": "traced lockstep batch telemetry (telemetry_bench)",
         "latest": snapshot, "history": history}, indent=1) + "\n")


def main() -> None:
    quick = "--quick" in sys.argv[1:]
    enable_compile_cache()
    print("name,value,derived")
    for name, value, derived in run(quick):
        print(f"{name},{value:.6g},{derived}")


if __name__ == "__main__":
    main()
