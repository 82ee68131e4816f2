"""Golden tests for the plan-lint static-analysis subsystem
(``repro.analysis``): every rule class is exercised on a deliberately
broken fixture (tests/fixtures_plan_lint.py) asserting the exact rule id
and location, and the shipped tree is asserted clean (zero false
positives) so the CI ``--fail-on warn`` gate stays meaningful.
"""
import json
from pathlib import Path

import pytest

import fixtures_plan_lint as fx
from repro.analysis.hotpath_lint import lint_file, lint_tree
from repro.analysis.jaxpr_lint import lint_cost_fn, lint_registered
from repro.analysis.recompile_audit import (EXPECTED_COMPILE_COUNTS, PROBES,
                                            audit_source, audit_sources,
                                            compare_counts,
                                            expected_compile_counts,
                                            fresh_backend, plan_devices,
                                            run_probes, table_hash)
from repro.analysis.registry import hot_path, iter_cost_surfaces
from repro.analysis.report import (Finding, apply_pragmas, parse_pragmas,
                                   pragma_findings, summarize)

FIXTURE_PATH = Path(fx.__file__).resolve()
FIXTURE_SRC = FIXTURE_PATH.read_text()
N_DIMS, P_WIDTH = 2, 2


def lint(fn, name):
    return lint_cost_fn(fn, N_DIMS, P_WIDTH, name=name)


def fixture_line(needle, exact=False):
    """1-based line of the first fixture source line containing needle."""
    for i, text in enumerate(FIXTURE_SRC.splitlines(), start=1):
        if (text.strip() == needle) if exact else (needle in text):
            return i
    raise AssertionError(f"marker {needle!r} not in fixture")


def only(findings):
    assert len(findings) == 1, [f.render() for f in findings]
    return findings[0]


# ------------------------- pass 1: jaxpr lint ------------------------------ #

def test_tracer_bool_branch():
    f = only(lint(fx.fn_tracer_bool, "fx/tracer-bool"))
    assert f.rule == "tracer-bool"
    assert f.severity == "error"
    assert f.path.endswith("tests/fixtures_plan_lint.py")
    assert f.line == fx.fn_tracer_bool.__code__.co_firstlineno


def test_weak_type_output():
    f = only(lint(fx.fn_weak_type, "fx/weak-type"))
    assert (f.rule, f.severity) == ("weak-type", "warn")
    assert f.line == fx.fn_weak_type.__code__.co_firstlineno


def test_low_precision_cast():
    f = only(lint(fx.fn_low_precision, "fx/f16"))
    assert (f.rule, f.severity) == ("dtype", "error")
    assert "float16" in f.message


def test_multi_output():
    f = only(lint(fx.fn_multi_output, "fx/multi"))
    assert (f.rule, f.severity) == ("dtype", "error")
    assert "2 outputs" in f.message


def test_wrong_shape_output():
    f = only(lint(fx.fn_wrong_shape, "fx/shape"))
    assert (f.rule, f.severity) == ("dtype", "error")
    assert "shape" in f.message
    assert f.line == fx.fn_wrong_shape.__code__.co_firstlineno


def test_integer_output():
    f = only(lint(fx.fn_int_output, "fx/int"))
    assert (f.rule, f.severity) == ("dtype", "error")
    assert "not float" in f.message


def test_cross_config_reduce():
    f = only(lint(fx.fn_cross_reduce, "fx/reduce"))
    assert (f.rule, f.severity) == ("cross-config-reduce", "error")
    assert f.line == fx.fn_cross_reduce.__code__.co_firstlineno


def test_scalar_closure_capture():
    fn = fx.make_fn_scalar_capture()
    f = only(lint(fn, "fx/capture"))
    assert (f.rule, f.severity) == ("closure-capture", "warn")
    assert f.line == fn.__code__.co_firstlineno


def test_clean_surface_has_no_findings():
    assert lint(fx.make_fn_clean(), "fx/clean") == []


def test_registered_surfaces_lint_clean():
    """Zero false positives on every shipped cost surface."""
    findings = lint_registered()
    assert findings == [], [f.render() for f in findings]
    names = {s.name for s in iter_cost_surfaces()}
    assert {"db/paper/SMJ", "db/paper/BHJ",
            "tpu/roofline/train", "tpu/roofline/decode"} <= names


# ---------------------- pass 3: hot-path host-sync ------------------------- #

@pytest.fixture(scope="module")
def hot_findings():
    return lint_file(FIXTURE_PATH)


def test_hot_loop_sync_is_warn(hot_findings):
    line = fixture_line("out.append(float(v))")
    f = only([f for f in hot_findings
              if f.obj == "hot_loop_sync" and f.severity == "warn"])
    assert f.rule == "host-sync"
    assert f.line == line
    assert not f.allowed


def test_hot_depth_zero_sync_is_info(hot_findings):
    line = fixture_line("return np.asarray(out)")
    f = only([f for f in hot_findings
              if f.obj == "hot_loop_sync" and f.severity == "info"])
    assert (f.rule, f.line) == ("host-sync", line)


def test_pragma_allows_with_reason(hot_findings):
    f = only([f for f in hot_findings if f.obj == "hot_allowed_fold"])
    assert f.rule == "host-sync"
    assert f.allowed
    assert "justified fold" in f.allow_reason


def test_cold_function_not_linted(hot_findings):
    assert not [f for f in hot_findings if f.obj == "cold_loop_sync"]


def test_sync_budget_overrun_warns_at_fn_head(hot_findings):
    """Two depth-zero syncs against folds=1 -> one sync-budget warn at
    the function head (plus the two underlying host-sync infos)."""
    line = fixture_line("def hot_over_budget(a, b):")
    fs = [f for f in hot_findings if f.obj == "hot_over_budget"]
    infos = [f for f in fs if f.rule == "host-sync"]
    assert len(infos) == 2
    assert all(f.severity == "info" for f in infos)
    f = only([f for f in fs if f.rule == "sync-budget"])
    assert (f.severity, f.line) == ("warn", line)
    assert "folds=1" in f.message


def test_host_tracked_decode_stays_in_budget(hot_findings):
    """float() on a name assigned from np.asarray is a free host read,
    not a device sync: only the asarray itself is flagged, the in-loop
    decode is silent, and the folds=1 budget holds."""
    fs = [f for f in hot_findings if f.obj == "hot_host_tracked_decode"]
    f = only(fs)
    assert (f.rule, f.severity) == ("host-sync", "info")
    assert "asarray" in f.message


def test_traced_hot_path_lints_clean(hot_findings):
    """Obs span/metric payloads are sync-free: a hot loop whose only
    float() decodes sit inside ``_obs`` calls yields zero findings, and
    the folds=0 budget proves pass 3 counted no syncs at all."""
    assert not [f for f in hot_findings if f.obj == "hot_traced_clean"]


def test_obs_exemption_does_not_leak(hot_findings):
    """A float() in the same loop as an ``_obs.instant`` call — but
    outside any obs call — must still warn."""
    line = fixture_line("out.append(float(c))")
    f = only([f for f in hot_findings
              if f.obj == "hot_traced_still_syncs"])
    assert (f.rule, f.severity, f.line) == ("host-sync", "warn", line)
    assert not f.allowed


def test_admission_loop_fixture_in_budget(hot_findings):
    """The streaming service's admission-loop shape: per-ticket obs
    payloads inside the loop are exempt, and the wave's single host
    readback fits the folds=1 budget — exactly one host-sync info, no
    warn and no sync-budget finding."""
    fs = [f for f in hot_findings if f.obj == "hot_admission_loop"]
    f = only(fs)
    assert (f.rule, f.severity) == ("host-sync", "info")
    assert f.line == fixture_line("wave = np.asarray(wave_costs)")
    assert not f.allowed


def test_reasonless_pragma_flagged(hot_findings):
    line = fixture_line("# plan-lint: allow(host-sync)", exact=True)
    f = only([f for f in hot_findings if f.rule == "pragma-no-reason"])
    assert (f.severity, f.line) == ("warn", line)


def test_shipped_tree_hot_paths_clean():
    """No unallowed warn+ host-sync findings in src/repro."""
    bad = [f for f in lint_tree()
           if not f.allowed and f.severity != "info"]
    assert bad == [], [f.render() for f in bad]


def test_hot_path_decorator_requires_reason():
    with pytest.raises(ValueError):
        hot_path("")

    @hot_path("why this is hot")
    def g(x):
        return x

    assert g(3) == 3
    assert g.__plan_lint_hot_reason__ == "why this is hot"


# ------------------- pass 2 (static): memo-key coverage -------------------- #

UNKEYED_SRC = '''\
class FakeBackend:
    def argmin(self, fn, cluster, nonce):
        def build():
            return nonce + 1
        return self._program("scan", fn, cluster, (), build)
'''

KEYED_SRC = UNKEYED_SRC.replace('(), build', '(nonce,), build')

DERIVED_SRC = '''\
class FakeBackend:
    def argmin(self, fn, cluster):
        grids = cluster.grids
        shape = tuple(len(g) for g in grids)
        def build():
            return shape
        return self._program("scan", fn, cluster, (), build)
'''


def test_unkeyed_static_arg_flagged(tmp_path):
    p = tmp_path / "fake_backend.py"
    p.write_text(UNKEYED_SRC)
    f = only(audit_source(p))
    assert (f.rule, f.severity) == ("unkeyed-static-arg", "warn")
    assert f.obj == "argmin"
    assert "'nonce'" in f.message
    assert f.line == 3  # the build() def


def test_keyed_static_arg_clean(tmp_path):
    p = tmp_path / "fake_backend.py"
    p.write_text(KEYED_SRC)
    assert audit_source(p) == []


def test_derivation_through_comprehension_is_covered(tmp_path):
    """Locals derived from keyed inputs via a genexp must not flag:
    comprehension-bound names are not free."""
    p = tmp_path / "fake_backend.py"
    p.write_text(DERIVED_SRC)
    assert audit_source(p) == []


def test_shipped_backend_sources_are_keyed():
    assert audit_sources() == []


# ------------------- pass 2 (dynamic): recompile audit --------------------- #

def test_compare_counts_churn_and_stale():
    # explicit expected table: device-count independent on purpose
    exp = EXPECTED_COMPILE_COUNTS["jax"]
    churn = dict(exp)
    churn[PROBES[0]] += 1
    f = only(compare_counts("jax", churn, exp))
    assert (f.rule, f.severity) == ("recompile-churn", "error")
    assert f.obj == f"jax.{PROBES[0]}"

    reuse = next(p for p in PROBES if exp[p] >= 1)
    stale = dict(exp)
    stale[reuse] -= 1
    f = only(compare_counts("jax", stale, exp))
    assert (f.rule, f.severity) == ("stale-program", "error")

    assert compare_counts("jax", dict(exp), exp) == []


def test_expected_counts_one_device_matches_legacy_table():
    for name in EXPECTED_COMPILE_COUNTS:
        assert expected_compile_counts(name, 1) \
            == EXPECTED_COMPILE_COUNTS[name], name


def test_expected_counts_eight_devices_collapse_geometry_classes():
    """Device-even padding is a memo-key component: at D=8 the churn
    probe's {8, 4} chunk sweep clips to one per-device share and the
    climb Q sweep pads to one class of 8, while Qpad still splits the
    stacked scan three ways.  Pure geometry — no jax needed."""
    exp = expected_compile_counts("jax", 8)
    assert exp["scan_chunk_churn"] == 1
    assert exp["climb_many_qpad"] == 1
    assert exp["scan_many_qpad"] == 3
    assert exp["grid_rekey"] == 2
    assert expected_compile_counts("jax_x64", 8) == exp
    assert expected_compile_counts("numpy", 8) \
        == EXPECTED_COMPILE_COUNTS["numpy"]


def test_numpy_backend_never_compiles():
    counts = run_probes(fresh_backend("numpy"))
    assert counts == EXPECTED_COMPILE_COUNTS["numpy"]
    assert set(counts) == set(PROBES)


def test_jax_backend_compile_counts_match_contract():
    pytest.importorskip("jax")
    counts = run_probes(fresh_backend("jax"))
    assert counts == expected_compile_counts("jax", plan_devices())


def test_table_hash_is_stable_and_sensitive():
    t = {"jax": {"p": 1}, "numpy": {"p": 0}}
    h = table_hash(t)
    assert h == table_hash({"numpy": {"p": 0}, "jax": {"p": 1}})
    assert h != table_hash({"jax": {"p": 2}, "numpy": {"p": 0}})
    assert len(h) == 12


# --------------------------- report / pragmas ------------------------------ #

def test_parse_pragmas_covers_own_and_next_line():
    src = "x = 1\n# plan-lint: allow(dtype, weak-type): known promotion\ny = 2\nz = 3\n"
    pragmas = parse_pragmas(src)
    assert set(pragmas) == {2, 3}
    rules, reason = pragmas[3]
    assert rules == ("dtype", "weak-type")
    assert reason == "known promotion"


def test_apply_pragmas_matches_rule_and_line():
    src = "# plan-lint: allow(dtype): fine here\ny = 2\n"
    hit = Finding(rule="dtype", severity="error", path="f.py", line=2,
                  obj="g", message="m")
    wrong_rule = Finding(rule="weak-type", severity="warn", path="f.py",
                         line=2, obj="g", message="m")
    far = Finding(rule="dtype", severity="error", path="f.py", line=4,
                  obj="g", message="m")
    apply_pragmas([hit, wrong_rule, far], {"f.py": src})
    assert hit.allowed and hit.allow_reason == "fine here"
    assert not wrong_rule.allowed
    assert not far.allowed


def test_summarize_excludes_allowed():
    a = Finding(rule="dtype", severity="error", path="f.py", line=1,
                obj="g", message="m", allowed=True, allow_reason="r")
    b = Finding(rule="host-sync", severity="warn", path="f.py", line=2,
                obj="g", message="m")
    s = summarize([a, b])
    assert s["by_severity"] == {"info": 0, "warn": 1, "error": 0}
    assert s["by_rule"] == {"host-sync": 1}
    assert s["allowed"] == 1 and s["total"] == 2


def test_pragma_findings_only_reasonless():
    src = ("# plan-lint: allow(dtype): ok\n"
           "# plan-lint: allow(dtype)\n")
    fs = pragma_findings("f.py", src)
    assert [f.line for f in fs] == [2]
    assert fs[0].rule == "pragma-no-reason"


# ------------------------------- CLI --------------------------------------- #

def test_cli_clean_tree_exits_zero(tmp_path):
    from repro.analysis.__main__ import main
    out = tmp_path / "plan_lint.json"
    rc = main(["--skip-audit", "--fail-on", "warn", "--json", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["summary"]["by_severity"]["warn"] == 0
    assert payload["summary"]["by_severity"]["error"] == 0
    assert {"findings", "summary", "compile_counts", "table_hash"} \
        <= set(payload)
