"""Finds the knee of an open-loop cell: the highest offered rate whose
backlog does not grow over the window.

    python3 bench/sweep.py --config <config> --traffic <mix> \
        --rates 40,80,120 --seconds 20 --seed 1

``--config`` names ``bench/configs/<config>.json`` and ``--traffic``
``bench/traffic/<mix>.json``: the rate is found before the cell that
fixes it is in ``BENCHMARK.json``.

One process on the chip: it builds the cell's deployment and warms it
once (scan widths and warm-up traffic, as a run does), then offers each
rate in turn for ``--seconds`` on a fresh service over the same broker,
and prints one line per rate: the arrivals due, how many were still
unresolved at the window's middle and at its close (the backlog), and
the p50/p95 latency from due time once drained.  A rate sustains when
the backlog grows over the window's second half by no more than a
quarter second of arrivals (5% of that half's); the knee is the highest
rate that sustains with every lower rate, and the last line gives it
with 0.8 x the knee.  The cell's rate is then fixed in its traffic file
as a number; runs never search for it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench.run_cell import (Deployment, Loop, device_info,  # noqa: E402
                            queries, widths_of, window_queries)
from bench.window import percentile  # noqa: E402

GROWTH_S = 0.25     # backlog growth allowed, in seconds of arrivals


def backlog(offers, due_ns, at_ns) -> int:
    """Arrivals due before ``at_ns`` and not resolved by then."""
    return sum(1 for o, d in zip(offers, due_ns) if d < at_ns and
               (o.resolve_ns is None or o.resolve_ns > at_ns))


def sustains(rate: float, backlog_mid: int, backlog_end: int) -> bool:
    return backlog_end - backlog_mid <= GROWTH_S * rate


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    config = spec.load_json(spec.BENCH / "configs" / f"{args.config}.json")
    traffic = spec.load_json(spec.BENCH / "traffic" / f"{args.traffic}.json")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    dev = device_info(jax)
    if dev["platform"] != "tpu":
        print(f"sweep: no TPU: JAX runs on {dev['platform']}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.service import StreamingPlannerService
    enable_compile_cache()
    dep = Deployment(config, traffic)
    dep.warm_widths(widths_of(traffic))
    wu = traffic["warmup"]
    Loop(dep, None).closed(iter(queries(dep.schema, int(wu["queries"]),
                                        int(wu["seed"]), traffic)),
                           int(wu["concurrency"]))
    dep.service.drain()
    knee, below = None, True
    for rate in sorted(float(r) for r in args.rates.split(",")):
        dep.service = StreamingPlannerService(
            dep.raqo, objective=config["cost_model"]["objective"])
        loop = Loop(dep, None)
        arrivals = window_queries(dep.schema,
                                  int(rate * args.seconds * 1.2) + 64,
                                  args.seed, traffic, rate=rate)
        start = time.perf_counter_ns()
        end = start + int(args.seconds * 1e9)
        offers = loop.open(arrivals, start, end)
        due = [o.due_ns for o in offers]
        lat = [o.latency_s for o in offers]
        mid = backlog(offers, due, (start + end) // 2)
        fin = backlog(offers, due, end)
        ok = sustains(rate, mid, fin)
        below = below and ok
        if below:
            knee = rate
        print(json.dumps({
            "rate": rate, "due": len(offers),
            "backlog_mid": mid, "backlog_end": fin, "sustains": ok,
            "waves": dep.service.waves,
            "p50_s": percentile(lat, 50), "p95_s": percentile(lat, 95),
            "drain_s": (max(o.resolve_ns for o in offers) - end) / 1e9}),
            flush=True)
    print(json.dumps({"knee": knee,
                      "rate": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
