"""The control of the correctness check: the reference one precision down.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

The reference is a file that the configuration names
(``check.reference``: ``bench/references/<name>.py``; where it names none,
``bench/reference.py``; ``spec.reference``).  This puts that reference in
the planner's place with its grid search in bfloat16 and its committed
costs in float32, the step below the float32 search and float64 commit
that the configuration states, plans the queries a run of the cell would
compare (the seed's window stream, sampled as ``run_cell.check`` samples,
the longest query among them), and judges the plans as a run judges the
planner's, by every key of ``check.limits``.  Each compared number is
printed beside its limit: the control has to fail at least one.  The
benchmark's own runs never run this; it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench.run_cell import judge, sample, window_queries  # noqa: E402
from bench.traffic import generator as gen  # noqa: E402
from bench.window import Offer  # noqa: E402

POOL_PER_SAMPLE = 4        # stream prefix drawn from, per compared query


def readings(config: dict, traffic: dict, seed: int) -> dict:
    """The control's worst value of each key of ``check.limits`` on
    ``seed``."""
    schema = gen.build_schema(traffic["schema"])
    k = int(config["check"]["sample_queries"])
    pool = window_queries(schema, POOL_PER_SAMPLE * k, seed, traffic)
    picked = sample([Offer(q.tables, 0) for q in pool], k, seed)
    reference = spec.reference(config)
    ref = reference(config, schema)
    ctl = reference(config, schema, precision="control")
    for planner in (ref, ctl):
        planner.prefetch(o.tables for o in picked)
    worst, _ = judge(ref, ((ctl.plan(o.tables), o.tables) for o in picked),
                     config["check"]["limits"])
    return dict(worst, compared=len(picked))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload, trace=False)
    limits = cell.config["check"]["limits"]
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        got = readings(cell.config, cell.traffic, seed)
        fails = [k for k in limits if got[k] > limits[k]]
        failed_all &= bool(fails)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "fails": fails, **got,
                          "limits": limits}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
