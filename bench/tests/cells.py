"""A benchmark cell at a size the CPU runs in seconds, for the tests."""
import copy
from pathlib import Path

from bench import spec

ROOT = Path(spec.BENCH).parent
TINY_DIMS = [{"name": "num_containers", "lo": 1, "hi": 100, "step": 1},
             {"name": "container_gb", "lo": 1, "hi": 10, "step": 1}]


# a cell queued for a later benchmark (its rate awaits steady sets on the
# chip): its configuration, mix and readers are in ``bench/`` already
QUEUED = {"grid1k.wide100.poisson": ("raqo-sec7-grid1k", "wide100.poisson",
                                     ["plan_p50_s", "plan_p95_s", "setup_s"])}


def _queued(name: str, bench: Path) -> spec.Cell:
    config, mix, metrics = QUEUED[name]
    config = spec.load_json(bench / "configs" / f"{config}.json")
    return spec.Cell(
        name=name, chips=1, config=config,
        traffic=spec.load_json(bench / "traffic" / f"{mix}.json"),
        metrics=[spec.Metric(m, "", "end_to_end", spec.reader(m, bench))
                 for m in metrics],
        reference=spec.reference(config, bench))


def tiny(name: str, trace: bool = False, root: Path = ROOT,
         bench: Path = Path(spec.BENCH)) -> spec.Cell:
    """Cell ``name`` on the paper's 1,000-point grid, with a short warm-up
    and few queries in flight; everything else as committed."""
    cell = _queued(name, bench) if name in QUEUED else \
        spec.load_cell(root, name, trace, bench=bench)
    cell.config = copy.deepcopy(cell.config)
    cell.config["cluster"]["dims"] = TINY_DIMS
    cell.config["check"]["sample_queries"] = 40
    t = cell.traffic = copy.deepcopy(cell.traffic)
    t["warmup"] = dict(t["warmup"], queries=24,
                       concurrency=min(8, t["warmup"]["concurrency"]))
    t["warm_widths"] = {"max": 8}
    if t["arrival"]["loop"] == "closed":
        t["arrival"] = dict(t["arrival"], concurrency=8)
        t["pool"] = 5000
    else:
        t["arrival"] = dict(t["arrival"], rate=20.0)
    return cell


def device(jax) -> dict:
    from bench.run_cell import device_info
    return device_info(jax)


def fast_randomized(config: dict) -> dict:
    """``config`` planned by FastRandomized and judged by its reference
    (``bench/references/fast_randomized.py``), with ``op_gap`` held to
    the limit of ``plan_gap``."""
    config = copy.deepcopy(config)
    config["planner"] = "fastrandomized"
    chk = config["check"]
    chk["reference"] = "fast_randomized"
    chk["limits"] = dict(chk["limits"], op_gap=chk["limits"]["plan_gap"])
    return config
