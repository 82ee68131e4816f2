"""Runs one benchmark cell once, on the chip, and prints its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration file holds the deployment (cluster grid, cost surfaces,
planner, backend) and ``bench/traffic/<traffic>.json`` the traffic mix.
One run is one process that holds the chip:

1. set-up: check for a TPU (there is no CPU fallback), turn on JAX's
   persistent compilation cache, build the planner service, compile every
   stacked scan width the cell's traffic reaches, and serve a fixed-seed
   warm-up prefix of the cell's own traffic;
2. the window: ``--seconds`` of the seed's stream, closed loop (a fixed
   number of queries in flight) or open loop (arrivals due at fixed
   times, whatever the planner's backlog);
3. with ``--trace 1`` the window runs under the profiler and the span
   tracer, and the per-layer metrics are read from them; otherwise the
   end-to-end metrics are reported;
4. once the window has closed and the device's memory peak is read, a
   sample of the plans resolved in the window is compared with the
   float64 reference that the configuration names (``bench/spec.py``),
   each number the reference returns against its limit in
   ``check.limits``.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``; its last
key, ``checks``, holds each compared number beside its limit, which the
last lines of stderr repeat.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import (Dict, Iterable, Iterator, List, Optional,  # noqa: E402
                    Tuple)

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402
from bench.traffic import generator as gen  # noqa: E402
from bench.window import Offer, Window, resolved_within  # noqa: E402

IDLE_SLEEP_S = 0.0005          # open loop: longest nap before an arrival


def say(tag: str, **fields) -> None:
    print(f"bench {tag} {json.dumps(fields, sort_keys=True)}", flush=True)


# ------------------------------ deployment --------------------------------- #

# what a planner service that declares no ``planners`` serves: its
# ``submit`` builds a Selinger session whatever the RAQO names
SERVED = ("selinger",)


class Deployment:
    """The system under test, built from a configuration and a mix.

    A configuration whose ``planner`` the service does not serve, or
    whose ``planner_params`` (each a keyword of ``RAQO``) the program
    does not take, fails here, before anything compiles or runs."""

    def __init__(self, config: dict, traffic: dict):
        from repro.core import schema as rschema
        from repro.core.cluster import ClusterConditions, ResourceDim
        from repro.core.cost_model import (HiveSimulator,
                                           simulator_cost_models)
        from repro.core.plan_broker import PlanBroker
        from repro.core.planning_backend import get_backend
        from repro.core.raqo import RAQO
        from repro.service import StreamingPlannerService

        served = getattr(StreamingPlannerService, "planners", SERVED)
        if config["planner"] not in served:
            raise ValueError(
                f"the configuration names the planner "
                f"{config['planner']!r}, which the planner service does not "
                f"serve (it serves {list(served)})")
        self.config, self.traffic = config, traffic
        self.schema = gen.build_schema(traffic["schema"])
        program_schema = rschema.Schema(
            {n: rschema.Relation(r.name, r.rows, r.row_bytes)
             for n, r in self.schema.relations.items()},
            [rschema.JoinEdge(e.a, e.b, e.selectivity)
             for e in self.schema.edges])
        self.cluster = ClusterConditions(dims=tuple(
            ResourceDim(d["name"], d["lo"], d["hi"], d["step"])
            for d in config["cluster"]["dims"]))
        cm = config["cost_model"]
        if cm["kind"] != "hive_simulator":
            raise ValueError(f"unknown cost model {cm['kind']!r}")
        models = simulator_cost_models(HiveSimulator(**cm["constants"]))
        self.backend = get_backend(config["backend"])
        self.raqo = RAQO(schema=program_schema, models=models,
                         cluster=self.cluster, planner=config["planner"],
                         resource_planning=config["resource_planning"],
                         backend=self.backend,
                         broker=PlanBroker(backend=self.backend),
                         **config.get("planner_params", {}))
        self.service = StreamingPlannerService(
            self.raqo, objective=cm["objective"])

    def cost_fns(self) -> list:
        """The cost-surface fns the service's requests carry (one per
        join implementation), the objects the backend keys programs by."""
        costing = self.raqo._costing(self.config["cost_model"]["objective"])
        return [costing._grid_fn(impl, self.backend)
                for impl in self.config["cost_model"]["impls"]]

    def warm_widths(self, widths: List[int]) -> float:
        """Run one stacked scan of every width with each cost fn, so that
        every program the window uses is built (and its executable in
        the compilation cache) before the window opens."""
        t0 = time.perf_counter()
        for fn in self.cost_fns():
            for q in widths:
                params = np.tile([[1.0, 2.0]], (q, 1))
                self.backend.argmin_grid_many(fn, self.cluster, params)
        return time.perf_counter() - t0


def widths_of(traffic: dict) -> List[int]:
    """Even stacked widths from the mix's ``warm_widths.max`` down to 2,
    less those its fixed-seed warm-up runs itself (``warm_widths.warmup``,
    counted in rehearsal), which the warm-up builds after these: largest
    first, so that should the backend's bounded program memo evict, it
    evicts the widths the traffic uses least."""
    w = traffic["warm_widths"]
    skip = {int(q) for q in w.get("warmup", ())}
    return [q for q in range(int(w["max"]) // 2 * 2, 1, -2) if q not in skip]


ARRIVAL_PROCESSES = ("poisson",)


def queries(schema, n: int, seed: int, traffic: dict,
            rate: float = 0.0) -> List[gen.Query]:
    arr = traffic["arrival"]
    if arr["loop"] == "open" and arr.get("process") not in ARRIVAL_PROCESSES:
        raise ValueError(f"unknown arrival process {arr.get('process')!r}")
    return gen.stream(schema, n, seed, tenants=int(traffic["tenants"]),
                      tables_range=tuple(traffic["tables_range"]),
                      rate=rate, gap_seed=int(arr.get("gap_seed", 0)))


def window_queries(schema, n: int, seed: int, traffic: dict,
                   rate: float = 0.0) -> List[gen.Query]:
    """The window's stream for ``seed``.  Where the mix names a
    ``fixed_set``, the queries are drawn from its seed and ``seed``
    permutes them within its blocks (due times stay in place); else they
    are drawn from ``seed``."""
    fixed = traffic.get("fixed_set")
    if fixed is None:
        return queries(schema, n, seed, traffic, rate)
    base = queries(schema, n, int(fixed["seed"]), traffic, rate)
    moved = gen.shuffled_in_blocks(base, int(fixed["block"]), seed)
    return [gen.Query(b.t, q.tenant, q.tables) for b, q in zip(base, moved)]


# ------------------------------- the loops --------------------------------- #

class Loop:
    """Drives the service: offers queries, steps waves, stamps offers."""

    def __init__(self, dep: Deployment, spans: Optional[list]):
        self.svc = dep.service
        self.spans = spans         # harness spans (traced run) or None

    def _span(self, name: str, t0: int) -> None:
        if self.spans is not None:
            self.spans.append((name, t0, time.perf_counter_ns()))

    def offer(self, q: gen.Query, due_ns: int) -> Offer:
        o = Offer(q.tables, due_ns)
        o.submit_ns = time.perf_counter_ns()
        o.ticket = self.svc.submit(q.tables, q.tenant)
        self._span("bench.submit", o.submit_ns)
        return o

    def step(self) -> None:
        t0 = time.perf_counter_ns()
        self.svc.step()
        self._span("bench.step", t0)

    def closed(self, feed: Iterator[gen.Query], concurrency: int,
               until_ns: Optional[int] = None) -> List[Offer]:
        """Keep ``concurrency`` queries in flight until ``feed`` runs out
        or, with ``until_ns``, until that time (no drain)."""
        offers: List[Offer] = []
        while True:
            if until_ns is not None and time.perf_counter_ns() >= until_ns:
                return offers
            while self.svc.active < concurrency:
                q = next(feed, None)
                if q is None:
                    return offers
                now = time.perf_counter_ns()
                offers.append(self.offer(q, now))
            self.step()

    def open(self, arrivals: List[gen.Query], start_ns: int,
             end_ns: int) -> List[Offer]:
        """Offer each arrival at its due time (``start_ns`` + its offset)
        until ``end_ns``; then offer what fell due before ``end_ns`` and
        is still waiting, and drain."""
        offers: List[Offer] = []
        due = [start_ns + int(q.t * 1e9) for q in arrivals]
        i, n = 0, len(arrivals)
        while True:
            now = time.perf_counter_ns()
            if now >= end_ns:
                break
            while i < n and due[i] <= now:
                offers.append(self.offer(arrivals[i], due[i]))
                i += 1
            if self.svc.active:
                self.step()
            elif i < n:
                t0 = time.perf_counter_ns()
                time.sleep(min(max(due[i] - t0, 0) / 1e9, IDLE_SLEEP_S))
                self._span("bench.idle", t0)
        while i < n and due[i] < end_ns:
            offers.append(self.offer(arrivals[i], due[i]))
            i += 1
        while self.svc.active:
            self.step()
        return offers


# ------------------------------- tracing ----------------------------------- #

class Traced:
    """The profiler and the program's span tracer around the window."""

    def __init__(self, jax, on: bool):
        self.jax, self.on = jax, on
        self.dir = tempfile.TemporaryDirectory(prefix="bench_trace_") \
            if on else None
        self.spans: Optional[list] = [] if on else None
        self.obs: Optional[List[dict]] = None
        self.mark_ns = 0
        self.stop_s = 0.0          # seconds stop_trace took to write it

    def __enter__(self):
        if not self.on:
            return self
        from repro.obs import get_metrics, get_tracer
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir.name, profiler_options=opts)
        with self.jax.profiler.TraceAnnotation("bench.mark"):
            self.mark_ns = time.perf_counter_ns()
        get_tracer().reset()
        get_metrics().reset()
        get_tracer().enable()
        return self

    def __exit__(self, *exc):
        if self.on:
            from repro.obs import get_tracer
            get_tracer().disable()
            t0 = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t0
            self.obs = get_tracer().spans()
        return False

    def reduce(self, window: Window) -> Optional[dict]:
        """Device busy time, top device ops and idle gaps by host span,
        over the window; None when the trace holds no device."""
        from bench import trace_reduce
        from repro.obs import get_tracer
        epoch = get_tracer()._epoch_ns
        host = [(e["name"], epoch + int(e["ts"] * 1000),
                 epoch + int((e["ts"] + e["dur"]) * 1000),
                 e["args"].get("depth", 0))
                for e in self.obs]
        host += [(n, a, b, -1) for n, a, b in self.spans]
        try:
            got = trace_reduce.from_profile_dir(
                self.dir.name, "bench.mark", self.mark_ns,
                (window.start_ns, window.end_ns), host)
        finally:
            self.dir.cleanup()
        return got


# ----------------------------- correctness --------------------------------- #

def check(dep: Deployment, window: Window, seed: int,
          reference: type) -> dict:
    """Compare a seeded sample of the window's resolved plans, with the
    longest queries in it, with the float64 ``reference`` (a ``Planner``
    class, ``spec.reference``)."""
    chk = dep.config["check"]
    limits = chk["limits"]
    resolved = window.resolved
    missing = len(window.counted) - len(resolved)
    no_plan = sum(1 for o in resolved if o.ticket.joint is None or
                  o.ticket.joint.plan is None)
    pick = sample(resolved, int(chk["sample_queries"]), seed)
    ref = reference(dep.config, dep.schema)
    ref.prefetch(o.tables for o in pick)
    worst, bad = judge(ref, ((None if o.ticket.joint is None else
                              o.ticket.joint.plan, o.tables)
                             for o in pick), limits)
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in worst}
    checks["compared"] = {"value": len(pick), "limit": 1}
    checks["unresolved"] = {"value": missing, "limit": 0}
    ok = (len(pick) >= 1 and missing == 0 and no_plan == 0 and
          all(worst[k] <= limits[k] for k in worst))
    return {"correct": ok, "failed": missing + no_plan + bad,
            "checks": checks, "searches": ref.searches}


def judge(ref, plans: Iterable[Tuple[object, Tuple[str, ...]]],
          limits: Dict[str, float]) -> Tuple[Dict[str, float], int]:
    """The worst value of each key of ``limits`` over ``ref.compare`` of
    each (plan, tables) of ``plans``, and how many plans read over a
    limit.  A key that the reference does not return is an error."""
    worst = dict.fromkeys(limits, 0.0)
    bad = 0
    for plan, tables in plans:
        got = ref.compare(plan, tables)
        absent = [k for k in limits if k not in got]
        if absent:
            raise KeyError(f"check.limits names {absent}, which the "
                           f"reference {type(ref).__module__} does not "
                           f"return (it returns {sorted(got)})")
        if any(got[k] > limits[k] for k in limits):
            bad += 1
        for k in limits:
            worst[k] = max(worst[k], got[k])
    return worst, bad


def sample(offers: List[Offer], k: int, seed: int) -> List[Offer]:
    """``k`` offers drawn with ``seed``; the first drawn of the longest
    queries is always among them."""
    if len(offers) <= k:
        return list(offers)
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(offers))
    longest = max(len(o.tables) for o in offers)
    first_long = next(int(i) for i in order
                      if len(offers[int(i)].tables) == longest)
    rest = [int(i) for i in order if int(i) != first_long][:k - 1]
    return [offers[first_long]] + [offers[i] for i in rest]


# --------------------------------- run ------------------------------------- #

def device_info(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak(jax) -> int:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Context:
    """What a metric reader reads: the window, the program's counters and
    spans over it, the reduced device trace and the deployment."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, jax,
        dev: dict) -> dict:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import get_metrics
    cache = enable_compile_cache()
    dep = Deployment(cell.config, cell.traffic)
    traffic = cell.traffic
    arr = traffic["arrival"]
    widths = widths_of(traffic)
    say("setup", device=dev, backend=dep.backend.name, compile_cache=cache,
        warmup_queries=traffic["warmup"]["queries"],
        warm_widths=[widths[-1], widths[0]] if widths else [],
        n_warm_widths=len(widths))

    warm_s = dep.warm_widths(widths)
    wu = traffic["warmup"]
    warm_feed = iter(queries(dep.schema, int(wu["queries"]), int(wu["seed"]),
                             traffic))
    traced = Traced(jax, trace)
    loop = Loop(dep, traced.spans)
    svc, broker = dep.service, dep.raqo.broker
    open_loop = arr["loop"] == "open"
    t_w = time.perf_counter()
    if open_loop:
        warm_offers = loop.closed(warm_feed, int(wu["concurrency"]))
        svc.drain()
        rate = float(arr["rate"])
        pool = window_queries(dep.schema, int(rate * seconds * 1.2) + 64,
                              seed, traffic, rate=rate)
    else:
        conc = int(arr["concurrency"])
        warm_offers = loop.closed(warm_feed, conc)
        pool = window_queries(dep.schema, int(traffic["pool"]), seed,
                              traffic)
    warmup_s = time.perf_counter() - t_w

    s0 = counts(broker, svc)
    with traced:
        start_ns = time.perf_counter_ns()
        setup_s = start_ns / 1e9 - T_PROCESS
        end_ns = start_ns + int(seconds * 1e9)
        if open_loop:
            offers = loop.open(pool, start_ns, end_ns)
            counted = [o for o in offers if o.due_ns < end_ns]
        else:
            feed = iter(pool)
            offers = loop.closed(feed, conc, until_ns=end_ns)
            if next(feed, None) is None:
                raise RuntimeError("the window used up the mix's pool of "
                                   f"{len(pool)} queries; raise 'pool'")
            counted = None
        built = get_metrics().counter("backend.programs_built").value
    s1 = counts(broker, svc)
    if counted is None:
        counted = resolved_within(warm_offers + offers, start_ns, end_ns)
    window = Window(start_ns=start_ns, end_ns=end_ns, counted=counted,
                    waves=s1["service_waves"] - s0["service_waves"],
                    open_loop=open_loop, setup_s=setup_s)
    t_r = time.perf_counter()
    reduced = traced.reduce(window) if trace else None
    reduce_s = time.perf_counter() - t_r
    say("window", seconds=window.seconds, counted=len(counted),
        resolved=len(window.resolved), waves=window.waves,
        warm_widths_s=warm_s, warmup_s=warmup_s, setup_s=setup_s,
        broker={k: s1[k] - s0[k] for k in s1})
    device = dict(dev, memory_peak_bytes=memory_peak(jax))
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        say("trace", busy_s=reduced["busy_s"], window_s=reduced["window_s"],
            ops_in_window=reduced["ops_in_window"], programs_built=built,
            stop_trace_s=traced.stop_s, reduce_s=reduce_s)

    ctx = Context(window=window, counts={k: s1[k] - s0[k] for k in s1},
                  programs_built=built if trace else None,
                  obs_spans=traced.obs,
                  trace=reduced, grid_size=dep.cluster.grid_size(),
                  config=cell.config, device=dev,
                  peaks=spec.peaks(dev["kind"]) if reduced else None)
    metrics = {}
    for m in cell.metrics:
        v = m.read(ctx)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}

    del svc, loop
    dep.service = None
    t_c = time.perf_counter()
    verdict = check(dep, window, seed, cell.reference)
    say("check", seconds=time.perf_counter() - t_c,
        reference_searches=verdict["searches"])
    out = {"correct": verdict["correct"], "attempted": len(counted),
           "failed": verdict["failed"], "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = verdict["checks"]
    return out


def counts(broker, svc) -> dict:
    st = broker.stats
    return {"requests": st.broker_requests,
            "dedup_hits": st.broker_dedup_hits,
            "broker_waves": st.broker_waves,
            "researches": st.broker_researches,
            "service_waves": svc.waves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload, bool(args.trace))
    # the compile cache lives in the checkout, at a fixed path, whatever
    # the environment names; JAX reads the variable when it is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    dev = device_info(jax)
    if dev["platform"] != "tpu":
        print(f"bench: no TPU: JAX runs on {dev['platform']}",
              file=sys.stderr)
        return 2
    if dev["count"] < cell.chips:
        print(f"bench: the cell needs {cell.chips} chips, JAX sees "
              f"{dev['count']}", file=sys.stderr)
        return 2
    out = run(cell, args.seed, args.seconds, bool(args.trace), jax, dev)
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
