"""Observability subsystem tests (repro.obs): tracer span semantics and
thread-safety, the allocation-free disabled fast path, histogram /
registry math, PlanningStats.merge field completeness, and — the PR's
load-bearing contracts — (a) tracing NEVER perturbs planning: disabled
vs enabled runs produce bit-identical plans, PlanningStats and broker
counters; (b) the trace reconciles exactly with the count-based
counters: ``wave_summary()`` wave geometry == ``counters_snapshot()``,
request-histogram count == broker requests, async wave intervals pair
up, and a pipelined ``flush_async`` wave's device interval encloses the
host work interleaved under it.  An 8-simulated-device subprocess lane
pins the same reconciliation with ``REPRO_TRACE=1`` set in the
environment (the import-time enablement path).
"""
import dataclasses
import gc
import json
import math
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core.cluster import (ClusterConditions, PlanningStats,
                                ResourceDim, paper_cluster)
from repro.core.plan_broker import PlanBroker, PlanRequest
from repro.core.raqo import RAQO
from repro.core.schema import random_query, random_schema
from repro.obs import (NULL_SPAN, Histogram, MetricsRegistry, Tracer,
                       attribution_md, get_metrics, get_tracer,
                       wave_summary, write_chrome_trace)

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture
def traced():
    """Enable the process-wide tracer+metrics for one test, with a fresh
    buffer, and restore the disabled/empty state afterwards so the rest
    of the suite keeps exercising the zero-overhead path."""
    tr, mx = get_tracer(), get_metrics()
    was = tr.enabled
    tr.reset()
    mx.reset()
    tr.enable()
    try:
        yield tr, mx
    finally:
        tr.enabled = was
        tr.reset()
        mx.reset()


# ------------------------------ tracer ------------------------------------- #

def test_disabled_tracer_returns_shared_null_span():
    tr = Tracer(enabled=False)
    sp = tr.span("x", cat="c", payload=1)
    assert sp is NULL_SPAN and sp is tr.span("y")
    assert not sp                      # falsy: guards attribution kwargs
    with sp as inner:
        assert inner.set(a=1) is NULL_SPAN
    tr.instant("i")
    tr.complete("c", 0)
    tr.async_begin("w", 1)
    tr.async_end("w", 1)
    assert tr.events() == []


def test_disabled_path_is_allocation_free():
    """The broker hot-loop pattern against a disabled tracer must not
    allocate: net allocated-block delta over 20k iterations stays at
    noise level (a per-iteration allocation would show up as thousands)."""
    tr = Tracer(enabled=False)
    reg = MetricsRegistry()
    stages = ("broker.stage1", "broker.dispatch", "broker.flush.sync",
              "broker.wave.execute", "broker.wave.commit", "backend.launch",
              "backend.program_build")

    def loop(n):
        for i in range(n):
            sp = tr.span("broker.dispatch.group", cat="broker")
            if sp:
                sp.set(mode="grid", q=i)
            with sp:
                pass
            # the wave-stage, sync-flush, launch and build sites, the
            # compile span stamped after the fact, and the always-on
            # counters (looked up by name at each increment)
            for name in stages:
                with tr.span(name, cat="broker") as sp:
                    if sp:
                        sp.set(wave=i)
            tr.complete("backend.compile", 0, cat="compile", end_ns=1)
            reg.counter("backend.launches").inc()
            reg.counter("backend.scan_spans").inc(3)
            reg.counter("broker.sweep_rows.async").inc(1000)

    loop(1000)                        # warm caches / lazy init
    gc.collect()
    before = sys.getallocatedblocks()
    loop(20_000)
    gc.collect()
    delta = sys.getallocatedblocks() - before
    assert abs(delta) < 50, delta


def test_span_nesting_depth_and_containment():
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="t") as so:
        so.set(k="v")
        with tr.span("inner", cat="t"):
            pass
    outer = tr.spans("outer")[0]
    inner = tr.spans("inner")[0]
    assert outer["args"]["depth"] == 0 and outer["args"]["k"] == "v"
    assert inner["args"]["depth"] == 1
    # child interval inside parent interval
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["ph"] == inner["ph"] == "X"


def test_complete_instant_async_events():
    tr = Tracer(enabled=True)
    import time
    t0 = time.perf_counter_ns()
    tr.complete("manual", t0, cat="c", n=3)
    tr.instant("mark", cat="c")
    tr.async_begin("wave", 7, size=4)
    tr.async_end("wave", 7)
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["X", "i", "b", "e"]
    assert evs[0]["args"]["n"] == 3 and evs[0]["dur"] >= 0
    b, e = evs[2], evs[3]
    assert b["id"] == e["id"] == "7"
    assert b["ts"] <= e["ts"]
    # reset drops everything and re-epochs
    tr.reset()
    assert tr.events() == []


def test_tracer_thread_safety_nested_spans():
    """8 threads x 50 nested span pairs: every event lands, and each
    thread's inner spans stay contained in that thread's outer spans
    (per-thread stacks must not cross-corrupt)."""
    tr = Tracer(enabled=True)
    n_threads, iters = 8, 50
    # all threads alive at once, so thread idents are distinct (idents
    # are reused once a thread exits)
    gate = threading.Barrier(n_threads)

    def work():
        gate.wait()
        for i in range(iters):
            with tr.span("outer", cat="t", i=i):
                with tr.span("inner", cat="t", i=i):
                    pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.spans()
    assert len(evs) == n_threads * iters * 2
    by_tid = {}
    for e in evs:
        by_tid.setdefault(e["tid"], []).append(e)
    assert len(by_tid) == n_threads
    for tid, tevs in by_tid.items():
        outers = [e for e in tevs if e["name"] == "outer"]
        inners = [e for e in tevs if e["name"] == "inner"]
        assert len(outers) == len(inners) == iters
        assert all(e["args"]["depth"] == 0 for e in outers)
        assert all(e["args"]["depth"] == 1 for e in inners)


# ------------------------------ metrics ------------------------------------ #

def test_histogram_empty_and_single_value():
    h = Histogram()
    assert math.isnan(h.percentile(50))
    assert math.isnan(h.mean())
    assert h.snapshot() == {"count": 0, "sum": 0.0}
    for _ in range(10):
        h.observe(2.5e-3)
    # all mass in one bucket, clamped to the exact observed extremes
    assert h.percentile(0) == pytest.approx(2.5e-3)
    assert h.percentile(50) == pytest.approx(2.5e-3)
    assert h.percentile(100) == pytest.approx(2.5e-3)
    assert h.mean() == pytest.approx(2.5e-3)


def test_histogram_percentile_interpolation_and_bounds():
    h = Histogram()
    vals = [10.0 ** (-6 + i / 25.0) for i in range(100)]   # 1us..~10ms
    for v in vals:
        h.observe(v)
    p50, p99 = h.percentile(50), h.percentile(99)
    assert min(vals) <= p50 <= p99 <= max(vals)
    exact50 = float(np.percentile(vals, 50))
    # bucket resolution: 4/decade -> within one bucket width (~78%)
    assert 0.4 * exact50 <= p50 <= 2.5 * exact50
    s = h.snapshot()
    assert s["count"] == 100 and s["min"] == min(vals)
    assert s["max"] == max(vals)


def test_histogram_merge_is_bucketwise_addition():
    a, b = Histogram(), Histogram()
    for v in (1e-4, 2e-4, 3e-4):
        a.observe(v)
    for v in (5e-2, 6e-2):
        b.observe(v)
    a.merge(b)
    assert a.count == 5
    assert a.sum == pytest.approx(6e-4 + 11e-2)
    assert a.min == 1e-4 and a.max == 6e-2
    c = Histogram(edges=(1.0, 2.0))
    with pytest.raises(AssertionError):
        a.merge(c)


def test_registry_get_or_create_snapshot_merge():
    r = MetricsRegistry()
    assert r.counter("c") is r.counter("c")
    r.counter("c").inc(3)
    r.gauge("g").set(1.5)
    r.histogram("h").observe(0.25)
    with pytest.raises(AssertionError):
        r.gauge("c")                  # name/type conflict
    snap = r.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["c"] == 3 and snap["g"] == 1.5
    assert snap["h"]["count"] == 1
    other = MetricsRegistry()
    other.counter("c").inc(2)
    other.counter("new").inc(1)
    other.histogram("h").observe(0.5)
    r.merge(other)
    assert r.counter("c").value == 5
    assert r.counter("new").value == 1
    assert r.histogram("h").count == 2
    r.reset()
    assert r.snapshot() == {}


# ----------------- PlanningStats.merge field completeness ------------------- #

def test_planning_stats_merge_covers_every_field():
    """Type-driven sentinel per dataclass field: a field added to
    PlanningStats but forgotten in ``merge`` keeps its default and fails
    here — no hand-maintained field list to rot."""
    a, b = PlanningStats(), PlanningStats()
    want = {}
    for i, f in enumerate(dataclasses.fields(PlanningStats)):
        sentinel = 100 + i
        if f.type in ("int", int):
            setattr(b, f.name, sentinel)
            want[f.name] = 2 * sentinel
        elif f.type in ("list", list):
            setattr(b, f.name, [sentinel])
            want[f.name] = [sentinel, sentinel]
        elif f.type in ("dict", dict):
            setattr(b, f.name, {"m|k": {"hits": sentinel}})
            want[f.name] = {"m|k": {"hits": 2 * sentinel,
                                    "misses": 0, "inserts": 0}}
        else:
            pytest.fail(f"unhandled PlanningStats field type: "
                        f"{f.name}: {f.type!r} — extend this test")
    a.merge(b)
    a.merge(b)                        # twice: catches copy-not-add bugs
    for name, expect in want.items():
        assert getattr(a, name) == expect, name


# -------------------- broker instrumentation (direct) ----------------------- #

def _batch_fn(cfgs, params):
    c = np.asarray(cfgs, dtype=np.float64)
    return (c[:, 0] - params[0]) ** 2 + 0.1 * c[:, 1]


def _commit_fn(target):
    return lambda cfg: float((cfg[0] - target) ** 2 + 0.1 * cfg[1])


def _req(target):
    cluster = ClusterConditions(dims=(ResourceDim("a", 1, 8),
                                      ResourceDim("b", 1, 4)))
    return PlanRequest(fn=_batch_fn, cluster=cluster,
                       params=np.asarray([target]),
                       commit_fn=_commit_fn(target), mode="grid")


def test_critical_path_none_when_disabled():
    broker = PlanBroker("numpy")
    fut = broker.submit(_req(3.0))
    fut.result()
    assert fut.submit_ns is None and fut.critical_path() is None


def test_critical_path_breakdown(traced):
    broker = PlanBroker("numpy")
    f1 = broker.submit(_req(3.0))
    f2 = broker.submit(_req(3.0))     # exact dup -> follower
    broker.flush()
    f3 = broker.submit(_req(3.0))     # memoized -> resolves at submit
    cp1, cp2, cp3 = (f.critical_path() for f in (f1, f2, f3))
    assert cp1["verdict"] == "leader" and cp1["wave"] == 1
    assert {"total_s", "queue_s", "execute_s", "commit_s"} <= cp1.keys()
    assert cp1["total_s"] >= 0 and cp1["queue_s"] >= 0
    assert cp2["verdict"] == "follower" and cp2["wave"] == 1
    assert cp3["verdict"] == "memo" and cp3["wave"] is None
    assert cp3["total_s"] >= 0 and "queue_s" not in cp3


def test_flush_async_wave_interval_encloses_interleaved_host_work(traced):
    """Double-buffered pipelining, visible in the trace: a marker span
    emitted *between* two flush_async calls must fall inside wave 1's
    async b..e interval (wave 1 commits only at the next flush), and
    every async begin has a matching end."""
    tr, _ = traced
    broker = PlanBroker("numpy", double_buffer=True)
    f1 = broker.submit(_req(2.0))
    broker.flush_async()              # dispatch wave 1, no sync
    with tr.span("host.enumerate", cat="test"):
        pass                          # host work overlapped under wave 1
    broker.submit(_req(5.0))
    broker.flush_async()              # commits wave 1, dispatches wave 2
    broker.flush()                    # commits wave 2
    assert f1.done

    evs = tr.events()
    begins = {e["id"]: e for e in evs if e["ph"] == "b"}
    ends = {e["id"]: e for e in evs if e["ph"] == "e"}
    assert set(begins) == set(ends) == {"1", "2"}
    marker = tr.spans("host.enumerate")[0]
    assert begins["1"]["ts"] <= marker["ts"]
    assert marker["ts"] + marker["dur"] <= ends["1"]["ts"]
    assert f1.critical_path()["verdict"] == "leader"


def test_parent_links_each_group_to_its_dispatch(traced):
    """Every complete event names the span open when it opened: each
    ``broker.dispatch.group`` is a child of its wave's ``broker.dispatch``,
    and every broker span carries the wave number."""
    tr, _ = traced
    broker = PlanBroker("numpy", double_buffer=True)
    broker.submit(_req(2.0))
    broker.submit(_req(5.0))
    broker.flush_async()
    broker.submit(_req(6.0))
    broker.flush()
    dispatch = {e["args"]["id"]: e for e in tr.spans("broker.dispatch")}
    groups = tr.spans("broker.dispatch.group")
    assert len(dispatch) == len(groups) == 2
    for g in groups:
        d = dispatch[g["args"]["parent"]]
        assert d["args"]["wave"] == g["args"]["wave"]
        assert d["args"]["depth"] + 1 == g["args"]["depth"]
    assert sorted(d["args"]["wave"] for d in dispatch.values()) == [1, 2]
    assert all("wave" in e["args"] for e in tr.spans()
               if e["name"].startswith("broker."))
    assert not tr.spans("broker.wave")


# -------------- synchronous flushes, swept rows, launches, compiles ---------- #

def test_result_on_a_pending_future_is_one_sync_flush_of_cause_result(
        traced):
    tr, mx = traced
    broker = PlanBroker("numpy")
    fut = broker.submit(_req(3.0))
    fut.result()
    syncs = tr.spans("broker.flush.sync")
    assert len(syncs) == 1
    a = syncs[0]["args"]
    assert (a["cause"], a["requests"], a["groups"], a["wave"]) == \
        ("result", 1, 1, 1)
    snap = mx.snapshot()
    assert snap["broker.sync_flushes.result"] == 1
    assert "broker.sync_flushes.explicit" not in snap
    broker.submit(_req(4.0))
    broker.flush()
    assert mx.counter("broker.sync_flushes.explicit").value == 1
    assert [e["args"]["cause"] for e in tr.spans("broker.flush.sync")] == \
        ["result", "explicit"]


def _only_at(target):
    """A cost surface infeasible everywhere but ``target``: a climb from
    the corners strands, so ``scan_fallback`` reruns it as a grid scan."""
    def fn(cfgs, params):
        c = np.asarray(cfgs, dtype=np.float64)
        hit = (c[:, 0] == target[0]) & (c[:, 1] == target[1])
        return np.where(hit, 1.0, np.inf) + 0.0 * params[0]
    return fn


def test_scan_fallback_retry_is_a_sync_flush_of_cause_retry(traced):
    tr, mx = traced
    cluster = ClusterConditions(dims=(ResourceDim("a", 1, 8),
                                      ResourceDim("b", 1, 4)))
    req = PlanRequest(fn=_only_at((4, 2)), cluster=cluster,
                      params=np.asarray([0.0]),
                      commit_fn=lambda cfg: 1.0 if cfg == (4, 2)
                      else math.inf,
                      mode="ensemble", scan_fallback=True)
    broker = PlanBroker("numpy")
    fut = broker.submit(req)
    broker.flush_async()
    assert fut.result() == ((4, 2), 1.0)
    retry = [e for e in tr.spans("broker.flush.sync")
             if e["args"]["cause"] == "retry"]
    assert len(retry) == 1 and retry[0]["args"]["wave"] == 1
    snap = mx.snapshot()
    assert snap["broker.sync_flushes.retry"] == 1
    # the climb sweeps no grid rows; the retry's grid scan sweeps them all
    assert snap["broker.sweep_rows.sync"] == cluster.grid_size()
    assert "broker.sweep_rows.async" not in snap


def test_sweep_rows_count_each_group_once_by_flush_kind(monkeypatch):
    """With tracing off, ``broker.sweep_rows.sync + .async`` equals the
    stacked grid groups dispatched times the rows each sweeps."""
    from repro.core.planning_backend import get_backend
    be = get_backend("numpy")
    groups = []
    orig = be.argmin_grid_many_async

    def spy(fn, cluster, params_many, **kw):
        groups.append(cluster.grid_size())
        return orig(fn, cluster, params_many, **kw)
    monkeypatch.setattr(be, "argmin_grid_many_async", spy)
    mx = get_metrics()
    mx.reset()
    try:
        _, broker = _run_lockstep(n_queries=6)
        snap = mx.snapshot()
    finally:
        mx.reset()
    rows = snap.get("broker.sweep_rows.sync", 0) + \
        snap.get("broker.sweep_rows.async", 0)
    assert groups and rows == sum(groups) == len(groups) * 192
    assert snap.get("broker.sweep_rows.async", 0) > 0


def _xp_fn(cfgs, p):
    """A cost surface written for either array namespace."""
    return (cfgs[:, 0] - p[0]) ** 2 + 0.1 * cfgs[:, 1]


@needs_jax
def test_launches_count_every_span_of_every_scan(monkeypatch):
    """Over a small lockstep run ``backend.launches`` equals the number
    of stacked scans dispatched, one program each, and
    ``backend.scan_spans`` the sum over them of ceil(grid / span), the
    span computed by hand from ``_many_chunk`` (the live-element cap
    lowered so that each scan loops over several spans)."""
    from repro.core import planning_backend as pb
    monkeypatch.setattr(pb, "MAX_LIVE_ELEMENTS", 256)
    be = pb.JaxPlanBackend()
    scans = []
    orig = be.argmin_grid_many_async

    def spy(fn, cluster, params_many, **kw):
        scans.append((len(params_many), cluster.grid_size()))
        return orig(fn, cluster, params_many, **kw)
    monkeypatch.setattr(be, "argmin_grid_many_async", spy)
    mx = get_metrics()
    mx.reset()
    try:
        _run_lockstep(n_queries=4, backend=be)
        launches = mx.counter("backend.launches").value
        spans = mx.counter("backend.scan_spans").value
    finally:
        mx.reset()
    D = be.device_count()
    want = sum(-(-total // (D * pb._many_chunk(total, pb._pad_even(q), D,
                                                pb.DEFAULT_CHUNK)))
               for q, total in scans)
    assert scans and want > len(scans)
    assert launches == len(scans)
    assert spans == want


@needs_jax
def test_fresh_width_compiles_once_and_a_repeat_does_not():
    """With tracing off, the first stacked scan of a width builds one
    program and compiles it alone (no separate fold program); the same
    width again neither builds nor compiles."""
    from repro.core.planning_backend import JaxPlanBackend
    assert not get_tracer().enabled
    be = JaxPlanBackend()
    cluster = paper_cluster(24, 8)
    mx = get_metrics()

    def counts():
        snap = mx.snapshot()
        return (snap.get("backend.programs_built", 0),
                snap.get("backend.compiles", 0))
    pm = np.arange(3.0)[:, None]
    b0, c0 = counts()
    first = be.argmin_grid_many(_xp_fn, cluster, pm)
    b1, c1 = counts()
    assert (b1 - b0, c1 - c0) == (1, 1)         # plan_scan_many_w4
    assert be.argmin_grid_many(_xp_fn, cluster, pm) == first
    assert counts() == (b1, c1)


@needs_jax
def test_stacked_scan_program_is_named_by_its_width():
    import jax.numpy as jnp
    from repro.core.planning_backend import JaxPlanBackend
    be = JaxPlanBackend()
    be.argmin_grid_many(_xp_fn, paper_cluster(24, 8), np.arange(3.0)[:, None])
    (prog,) = [v[1] for k, v in be._programs.items() if k[0] == "scan_many"]
    text = prog.lower(0, jnp.zeros((4, 1), jnp.float32)).as_text()
    assert text.startswith("module @jit_plan_scan_many_w4 ")


@needs_jax
def test_traced_compile_and_launch_spans_nest_under_the_launch(traced):
    """Traced, a first launch's XLA compile is a ``backend.compile`` span
    (its jax stamps moved onto the tracer's clock) inside the
    ``backend.launch`` span that triggered it."""
    from repro.core.planning_backend import JaxPlanBackend
    tr, _ = traced
    be = JaxPlanBackend()
    be.argmin_grid_many(_xp_fn, paper_cluster(24, 8), np.arange(5.0)[:, None])
    (launch,) = tr.spans("backend.launch")
    assert launch["args"]["kind"] == "scan_many"
    assert launch["args"]["Qpad"] == 6 and launch["args"]["launches"] == 1
    assert launch["args"]["spans"] == 1
    inner = [e for e in tr.spans("backend.compile")
             if e["args"]["parent"] == launch["args"]["id"]]
    assert [e["args"]["fun_name"] for e in inner] == \
        ["jit(plan_scan_many_w6)"]
    c = inner[0]
    assert launch["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= launch["ts"] + launch["dur"] + 1.0


# ----------------------- invariance & reconciliation ------------------------ #

def _plan_sig(p):
    if p is None:
        return None
    if p.is_leaf:
        return tuple(sorted(p.tables))
    return (p.impl, tuple(p.resources), p.op_cost, p.total_cost,
            _plan_sig(p.left), _plan_sig(p.right))


def _run_lockstep(n_queries=8, backend="numpy"):
    schema = random_schema(8, seed=3)
    queries = [random_query(schema, 2 + q % 4, seed=q)
               for q in range(n_queries)]
    broker = PlanBroker(backend)
    r = RAQO(schema, cluster=paper_cluster(24, 8),
             resource_planning="batched", backend=backend, broker=broker)
    return r.plan_queries(queries), broker


def test_tracing_never_perturbs_planning():
    """Bit-identical plans, PlanningStats and broker counters with the
    tracer off vs on — the zero-interference contract CI pins with the
    REPRO_TRACE env var flipped across runs."""
    tr, mx = get_tracer(), get_metrics()
    was = tr.enabled
    tr.disable()
    try:
        base, b_broker = _run_lockstep()
        tr.reset()
        mx.reset()
        tr.enable()
        traced, t_broker = _run_lockstep()
    finally:
        tr.enabled = was
        tr.reset()
        mx.reset()
    assert [_plan_sig(a.plan) for a in base] == \
        [_plan_sig(a.plan) for a in traced]
    assert [a.exec_time for a in base] == [a.exec_time for a in traced]
    assert [dataclasses.asdict(a.stats) for a in base] == \
        [dataclasses.asdict(a.stats) for a in traced]
    assert b_broker.counters_snapshot() == t_broker.counters_snapshot()


def test_wave_spans_reconcile_with_counters(traced, tmp_path):
    """The trace and the counters describe the same run: wave_summary()
    geometry == counters_snapshot(), request-histogram count == broker
    requests, per-stage histograms match the dispatched-wave count, and
    the exported chrome trace is valid JSON with balanced async pairs."""
    tr, mx = traced
    plans, broker = _run_lockstep(n_queries=8)
    cs = broker.counters_snapshot()
    ws = wave_summary(tr, mx)

    assert ws["waves"] == cs["waves"] > 0
    assert ws["wave_sizes"] == cs["wave_sizes"]
    assert ws["max_wave"] == cs["max_wave"]
    assert ws["mean_wave"] == pytest.approx(cs["mean_wave"], abs=1e-3)
    assert ws["request"]["count"] == cs["requests"]
    assert ws["wave_stage1"]["count"] == cs["waves"]
    # execute/commit fire once per *dispatched* wave (an all-cache-hit
    # wave assembles but never reaches the device)
    assert ws["wave_execute"]["count"] == ws["wave_commit"]["count"]
    assert 0 < ws["wave_execute"]["count"] <= cs["waves"]
    for stage in ("request", "wave_stage1", "wave_execute",
                  "wave_commit"):
        s = ws[stage]
        assert s["p50_s"] <= s["p99_s"]

    # every future reports a critical path, and per-wave request counts
    # recovered from the stamps match the wave sizes
    per_wave = {}
    for sp in tr.spans("broker.stage1"):
        per_wave[sp["args"]["wave"]] = sp["args"]["size"]
    assert sorted(per_wave) == list(range(1, cs["waves"] + 1))

    # exporters: valid Perfetto JSON, balanced async pairs, and the
    # attribution table carries one row per query
    path = write_chrome_trace(tmp_path / "trace.json", tr)
    doc = json.loads(path.read_text())
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"
    begins = sorted(e["id"] for e in doc["traceEvents"] if e["ph"] == "b")
    ends = sorted(e["id"] for e in doc["traceEvents"] if e["ph"] == "e")
    assert begins == ends
    md = attribution_md(plans, tr, mx)
    assert md.count("\n| ") >= len(plans)
    assert "## Broker critical path" in md


# ------------------ 8-simulated-device lane (REPRO_TRACE=1) ----------------- #

_TRACED_DRIVER = """
import json, sys
import jax
from repro.core.cluster import paper_cluster
from repro.core.plan_broker import PlanBroker
from repro.core.raqo import RAQO
from repro.core.schema import random_query, random_schema
from repro.obs import get_tracer, wave_summary

assert jax.device_count() == 8, jax.device_count()
assert get_tracer().enabled          # REPRO_TRACE=1 import-time path

schema = random_schema(8, seed=3)
queries = [random_query(schema, k, seed=q)
           for q, k in enumerate((5, 3, 1, 4, 5))]
broker = PlanBroker("jax")
raqo = RAQO(schema, cluster=paper_cluster(24, 8), backend="jax",
            resource_planning="batched", broker=broker)
plans = raqo.plan_queries(queries)
cs = broker.counters_snapshot()
ws = wave_summary()
out = {"devices": jax.device_count(),
       "planned": sum(p.plan is not None for p in plans),
       "waves_match": ws["waves"] == cs["waves"] > 0,
       "sizes_match": ws["wave_sizes"] == cs["wave_sizes"],
       "requests_match": ws["request"]["count"] == cs["requests"],
       "programs_built": ws["programs_built"],
       "events": len(get_tracer().events())}
out["ok"] = (out["planned"] == len(queries) and out["waves_match"]
             and out["sizes_match"] and out["requests_match"]
             and out["programs_built"] > 0 and out["events"] > 0)
print(json.dumps(out))
"""


@needs_jax
def test_traced_lockstep_at_8_simulated_devices():
    """Device-sharded lane with tracing enabled via the environment:
    wave spans, request histogram and compile counters must reconcile
    with the broker counters at 8 simulated XLA devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_TRACE"] = "1"
    env.pop("REPRO_PLAN_DEVICES", None)
    proc = subprocess.run(
        [sys.executable, "-c", _TRACED_DRIVER],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"], out
