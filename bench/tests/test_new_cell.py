"""A new configuration, mix and metric make a new cell by adding files and
BENCHMARK.json entries alone."""
import hashlib
import json
import shutil

import jax

from bench import run_cell
from bench.tests import cells


def _digest(tree):
    return {p.relative_to(tree).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted(tree.rglob("*")) if p.is_file()
        and "__pycache__" not in p.parts}


def _copy(tmp_path):
    """A copy of the benchmark under ``tmp_path``: its ``bench`` dir."""
    bench = tmp_path / "bench"
    shutil.copytree(cells.ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return bench


def test_new_files_make_a_new_cell(tmp_path):
    bench = _copy(tmp_path)
    before = _digest(bench)

    cfg = json.loads((bench / "configs" / "raqo-sec7-grid1k.json")
                     .read_text())
    cfg["name"] = "tiny-grid"
    cfg["cluster"]["dims"] = cells.TINY_DIMS
    cfg["check"]["sample_queries"] = 30
    (bench / "configs" / "tiny-grid.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "recur16.closed256.json")
                     .read_text())
    mix.update(name="recur8.closed4", pool=5000,
               schema=dict(mix["schema"], n_tables=8),
               arrival={"loop": "closed", "concurrency": 4},
               warmup={"seed": 1, "queries": 16, "concurrency": 4},
               warm_widths={"max": 6})
    (bench / "traffic" / "recur8.closed4.json").write_text(json.dumps(mix))
    (bench / "metrics" / "test.waves_per_s.py").write_text(
        "def read(ctx):\n    return ctx.window.waves / ctx.window.seconds\n")
    spec_json = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec_json["configs"].append(
        {"name": "tiny-grid", "source": "test", "reduced": [],
         "file": "bench/configs/tiny-grid.json", "why": "test"})
    spec_json["workloads"].append(
        {"name": "tiny.recur8", "config": "tiny-grid",
         "traffic": "recur8.closed4", "chips": 1, "why": "test"})
    spec_json["per_layer"].append(
        {"name": "test.waves_per_s", "unit": "waves/s", "better": "higher",
         "source": "host_clock", "layer": "service", "moves": "plans_per_s",
         "workloads": ["tiny.recur8"]})
    spec_json["end_to_end"][0]["workloads"].append("tiny.recur8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec_json))

    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())

    cell = cells.tiny("tiny.recur8", trace=True, root=tmp_path, bench=bench)
    assert [m.name for m in cell.metrics][-1] == "test.waves_per_s"
    out = run_cell.run(cell, 5, 1.5, True, jax, cells.device(jax))
    assert out["correct"], out["checks"]
    assert out["metrics"]["test.waves_per_s"]["value"] > 0
    e2e = cells.tiny("tiny.recur8", trace=False, root=tmp_path, bench=bench)
    assert {m.name for m in e2e.metrics} == {"plans_per_s", "setup_s"}


def test_every_metric_has_a_reader_in_cells_that_report_what_it_moves():
    from bench import spec
    bj = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    cells_of = {m["name"]: set(m.get("workloads", [w["name"] for w in
                                                  bj["workloads"]]))
                for m in bj["end_to_end"]}
    for m in bj["end_to_end"] + bj["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for m in bj["per_layer"]:
        assert set(m["workloads"]) <= cells_of[m["moves"]], m["name"]


NEW_REFERENCE = """
from bench.reference import Planner as Selinger


class Planner(Selinger):
    def compare(self, plan, tables):
        return dict(super().compare(plan, tables), tables_judged=0.0)
"""


def test_a_configuration_names_its_reference_by_files_alone(tmp_path):
    bench = _copy(tmp_path)
    before = _digest(bench)

    (bench / "references" / "selinger_plus.py").write_text(NEW_REFERENCE)
    cfg = json.loads((bench / "configs" / "raqo-sec7-grid1k.json")
                     .read_text())
    cfg["name"] = "tiny-named"
    cfg["check"]["reference"] = "selinger_plus"
    cfg["check"]["limits"]["tables_judged"] = 0.0
    (bench / "configs" / "tiny-named.json").write_text(json.dumps(cfg))
    spec_json = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec_json["configs"].append(
        {"name": "tiny-named", "source": "test", "reduced": [],
         "file": "bench/configs/tiny-named.json", "why": "test"})
    spec_json["workloads"].append(
        {"name": "tiny.named", "config": "tiny-named",
         "traffic": "recur16.closed256", "chips": 1, "why": "test"})
    spec_json["end_to_end"][0]["workloads"].append("tiny.named")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec_json))

    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())
    assert after.keys() - before.keys() == {"references/selinger_plus.py",
                                            "configs/tiny-named.json"}

    cell = cells.tiny("tiny.named", root=tmp_path, bench=bench)
    assert cell.reference.__module__ == "bench_reference_selinger_plus"
    out = run_cell.run(cell, 5, 1.5, False, jax, cells.device(jax))
    assert out["correct"], out["checks"]
    assert list(out["checks"])[:3] == ["plan_gap", "cost_gap",
                                       "tables_judged"]
