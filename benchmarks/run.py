"""Benchmark harness: one function per paper table/figure + the roofline
table.  Prints ``name,us_per_call,derived`` CSV and archives JSON.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig13      # substring filter
    PYTHONPATH=src python -m benchmarks.run --trace    # traced lockstep
    PYTHONPATH=src python -m benchmarks.run --report   # trend report

``--trace`` runs the observability bench (benchmarks/telemetry_bench):
one traced lockstep batch, exporting the Perfetto trace + attribution
table + telemetry summary under ``artifacts/`` (``--quick`` shrinks the
workload and skips the tracked-history append, same contract as the
other benches).

``--report`` merges every ``BENCH_*.json`` at the repo root plus
``artifacts/bench_results.json`` into one trajectory report
(``artifacts/bench_report.json`` + ``.md``): a flat metric table for the
current state and, for bench files that append per-run ``history``
snapshots (resource_planning_bench and telemetry_bench do), a trend
table across runs/PRs — every numeric snapshot key is trended
automatically, so the ``lockstep_*`` cross-query planning keys ride
along with no changes here.  A "## telemetry" section summarizes the
latest traced run (request p50/p99 and the wave
assembly/execute/commit split), and a "## streaming" section the latest
streaming-service run (plans/sec and submit->resolve p50/p99 from
benchmarks/streaming_bench — the CI latency gate's numbers).
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _flatten(prefix: str, obj, rows: list) -> None:
    """Flatten nested dicts/lists of scalars into (metric, value) rows."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, rows)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _flatten(f"{prefix}[{i}]", v, rows)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        rows.append((prefix, float(obj)))


def _lint_summary(sources: list) -> dict:
    """plan-lint rule/severity counts + compile-count table hash for the
    report.  Prefers the CI artifact (artifacts/plan_lint.json, written
    by ``python -m repro.analysis --json``); falls back to the last
    snapshot in the tracked BENCH_plan_lint.json history."""
    artifact = ROOT / "artifacts" / "plan_lint.json"
    if artifact.exists():
        try:
            data = json.loads(artifact.read_text())
            s = data.get("summary", {})
            sources.append("artifacts/plan_lint.json")
            return {"source": "artifacts/plan_lint.json",
                    "by_severity": s.get("by_severity", {}),
                    "by_rule": s.get("by_rule", {}),
                    "allowed": s.get("allowed", 0),
                    "table_hash": data.get("table_hash")}
        except (json.JSONDecodeError, TypeError):
            pass
    tracked = ROOT / "BENCH_plan_lint.json"
    if tracked.exists():
        try:
            data = json.loads(tracked.read_text())
            hist = data.get("history") or [{}]
            snap = hist[-1]
            return {"source": "BENCH_plan_lint.json (last snapshot)",
                    "by_severity": {k: snap[k] for k in
                                    ("info", "warn", "error") if k in snap},
                    "by_rule": {},
                    "allowed": snap.get("allowed", 0),
                    "table_hash": data.get("table_hash")}
        except (json.JSONDecodeError, TypeError, IndexError):
            pass
    return {}


def _telemetry_summary(sources: list) -> dict:
    """Latest traced-run digest for the report: wave p50/p99 and the
    per-stage split.  Prefers the fresh artifact
    (artifacts/telemetry_summary.json, written by ``--trace``); falls
    back to the last snapshot in the tracked BENCH_telemetry.json
    history (same pattern as ``_lint_summary``)."""
    artifact = ROOT / "artifacts" / "telemetry_summary.json"
    if artifact.exists():
        try:
            data = json.loads(artifact.read_text())
            sources.append("artifacts/telemetry_summary.json")
            req = data.get("request", {})
            return {"source": "artifacts/telemetry_summary.json",
                    "requests": req.get("count", 0),
                    "request_p50_s": req.get("p50_s"),
                    "request_p99_s": req.get("p99_s"),
                    "wave_stage1_mean_s":
                        data.get("wave_stage1", {}).get("mean_s"),
                    "wave_dispatch_mean_s":
                        data.get("wave_dispatch", {}).get("mean_s"),
                    "wave_execute_mean_s":
                        data.get("wave_execute", {}).get("mean_s"),
                    "wave_commit_mean_s":
                        data.get("wave_commit", {}).get("mean_s"),
                    "waves": data.get("waves"),
                    "max_wave": data.get("max_wave"),
                    "programs_built": data.get("programs_built"),
                    "compiles": data.get("compiles")}
        except (json.JSONDecodeError, TypeError):
            pass
    tracked = ROOT / "BENCH_telemetry.json"
    if tracked.exists():
        try:
            data = json.loads(tracked.read_text())
            snap = (data.get("history") or [{}])[-1]
            keep = ("requests", "request_p50_s", "request_p99_s",
                    "wave_stage1_mean_s", "wave_dispatch_mean_s",
                    "wave_execute_mean_s", "wave_commit_mean_s", "waves",
                    "max_wave", "programs_built", "compiles")
            out = {k: snap.get(k) for k in keep}
            out["source"] = "BENCH_telemetry.json (last snapshot)"
            return out
        except (json.JSONDecodeError, TypeError, IndexError):
            pass
    return {}


def _streaming_summary(sources: list) -> dict:
    """Latest streaming-service digest: plans/sec and submit->resolve
    p50/p99 at smoke and full concurrency.  Prefers the fresh artifact
    (artifacts/streaming_summary.json, written by every
    streaming_bench run); falls back to the last snapshot in the
    tracked BENCH_streaming.json history (same pattern as
    ``_telemetry_summary``)."""
    keep = ("smoke_numpy_p50_s", "smoke_numpy_p99_s",
            "smoke_numpy_plans_per_s", "smoke_jax_p99_s",
            "closed_numpy_plans_per_s", "closed_numpy_p50_s",
            "closed_numpy_p99_s", "closed_jax_plans_per_s",
            "closed_jax_p99_s", "closed_concurrency",
            "open_jax_p99_s", "traced_request_p99_s", "traced_requests")
    artifact = ROOT / "artifacts" / "streaming_summary.json"
    if artifact.exists():
        try:
            data = json.loads(artifact.read_text())
            sources.append("artifacts/streaming_summary.json")
            out = {k: data.get(k) for k in keep if data.get(k) is not None}
            out["source"] = "artifacts/streaming_summary.json"
            return out
        except (json.JSONDecodeError, TypeError):
            pass
    tracked = ROOT / "BENCH_streaming.json"
    if tracked.exists():
        try:
            snap = (json.loads(tracked.read_text()).get("history")
                    or [{}])[-1]
            out = {k: snap.get(k) for k in keep if snap.get(k) is not None}
            out["source"] = "BENCH_streaming.json (last snapshot)"
            return out
        except (json.JSONDecodeError, TypeError, IndexError,
                AttributeError):
            pass
    return {}


def report() -> None:
    """Merge BENCH_*.json + artifacts/bench_results.json into one
    markdown/JSON trend table (the cross-PR perf trajectory)."""
    metrics: list = []
    trends: dict = {}
    sources: list = []
    for f in sorted(ROOT.glob("BENCH_*.json")):
        try:
            data = json.loads(f.read_text())
        except json.JSONDecodeError:
            continue
        sources.append(f.name)
        history = data.pop("history", None) if isinstance(data, dict) \
            else None
        _flatten(f.stem, data, metrics)
        if history:
            keys = sorted({k for snap in history for k, v in snap.items()
                           if isinstance(v, (int, float))
                           and not isinstance(v, bool)})
            trends[f.stem] = {
                "runs": [str(snap.get("ts", f"run{i}"))
                         for i, snap in enumerate(history)],
                "series": {k: [snap.get(k) for snap in history]
                           for k in keys},
            }
    bench_results = ROOT / "artifacts" / "bench_results.json"
    if bench_results.exists():
        try:
            rows = json.loads(bench_results.read_text())
            sources.append("artifacts/bench_results.json")
            for r in rows:
                # skip only the harness's ERROR sentinel rows, not any
                # legitimately negative metric
                if isinstance(r, dict) and \
                        isinstance(r.get("value"), (int, float)) and \
                        not str(r.get("derived", "")).startswith("ERROR"):
                    metrics.append((r["name"], float(r["value"])))
        except (json.JSONDecodeError, TypeError, KeyError):
            pass

    lint = _lint_summary(sources)
    telemetry = _telemetry_summary(sources)
    streaming = _streaming_summary(sources)

    payload = {"generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
               "sources": sources,
               "metrics": [{"name": n, "value": v} for n, v in metrics],
               "trends": trends,
               "plan_lint": lint,
               "telemetry": telemetry,
               "streaming": streaming}
    out_dir = ROOT / "artifacts"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "bench_report.json").write_text(
        json.dumps(payload, indent=1) + "\n")

    md = ["# Bench trajectory report", "",
          f"Generated {payload['generated']} from: "
          + ", ".join(sources), "", "## Current metrics", "",
          "| metric | value |", "|---|---|"]
    md += [f"| {n} | {v:.6g} |" for n, v in metrics]
    for stem, t in trends.items():
        md += ["", f"## Trend: {stem}", "",
               "| metric | " + " | ".join(t["runs"]) + " |",
               "|---|" + "---|" * len(t["runs"])]
        for k, series in t["series"].items():
            cells = " | ".join("" if v is None else f"{v:.6g}"
                               for v in series)
            md.append(f"| {k} | {cells} |")
    if lint:
        md += ["", "## plan-lint", "",
               f"Source: {lint['source']}  —  compile-count table hash "
               f"`{lint.get('table_hash') or 'n/a'}`", "",
               "| severity / rule | count |", "|---|---|"]
        md += [f"| {k} | {v:g} |"
               for k, v in sorted(lint["by_severity"].items())]
        md += [f"| {k} | {v:g} |" for k, v in sorted(lint["by_rule"].items())]
        md += [f"| allowed (pragma) | {lint['allowed']:g} |"]
    if telemetry:
        md += ["", "## telemetry", "",
               f"Source: {telemetry.pop('source', 'n/a')}", "",
               "| metric | value |", "|---|---|"]
        md += [f"| {k} | {'' if v is None else format(v, '.6g')} |"
               for k, v in telemetry.items()]
    if streaming:
        md += ["", "## streaming", "",
               f"Source: {streaming.pop('source', 'n/a')}", "",
               "| metric | value |", "|---|---|"]
        md += [f"| {k} | {'' if v is None else format(v, '.6g')} |"
               for k, v in streaming.items()]
    (out_dir / "bench_report.md").write_text("\n".join(md) + "\n")
    print(f"wrote {out_dir / 'bench_report.json'} and .md "
          f"({len(metrics)} metrics, {len(trends)} trend series)")


def main() -> None:
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    if "--report" in sys.argv[1:]:
        report()
        return
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--trace" in sys.argv[1:]:
        from benchmarks import telemetry_bench
        print("name,value,derived")
        for name, value, derived in \
                telemetry_bench.run("--quick" in sys.argv[1:]):
            print(f"{name},{value:.6g},{derived}")
        return
    from benchmarks import (paper_figs, resource_planning_bench,
                            roofline_table, tpu_planner)

    pattern = sys.argv[1] if len(sys.argv) > 1 else ""
    fns = list(paper_figs.ALL) + [resource_planning_bench.run,
                                  roofline_table.run, tpu_planner.run]
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for fn in fns:
        label = f"{fn.__module__.split('.')[-1]}.{fn.__name__}"
        if pattern and pattern not in label:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
            us = (time.perf_counter() - t0) * 1e6
            for name, value, derived in rows:
                print(f"{name},{value:.6g},{derived}")
                all_rows.append({"name": name, "value": value,
                                 "derived": derived})
            print(f"{label}._total,{us:.0f},bench wall time (us)")
        except Exception as e:  # run the other benches, then fail
            traceback.print_exc()
            print(f"{label}.ERROR,-1,{type(e).__name__}: {e}")
            all_rows.append({"name": label, "value": -1,
                             "derived": f"ERROR {e}"})
            failed.append(label)
    out = Path(__file__).resolve().parent.parent / "artifacts"
    out.mkdir(exist_ok=True)
    (out / "bench_results.json").write_text(json.dumps(all_rows, indent=1))
    if failed:
        sys.exit(f"{len(failed)} bench(es) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
