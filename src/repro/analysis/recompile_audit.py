"""Pass 2 — recompile / memo-key audit of the compiled-program caches.

Two halves, attacking the bug class behind the flush-size recompile
churn that Q-padding fixed (a runtime-varying input that is — or is not
— part of the ``JaxPlanBackend._program`` memo key):

**Dynamic sweep.**  A fresh (non-singleton) instance of each backend
runs a fixed probe battery on tiny grids with ``build()`` invocations
counted, deriving the *actual* compile count per probe.  The battery
sweeps exactly the runtime-varying inputs the memo key must cover:
request params (must NOT rebuild), chunk geometry, stacked flush size Q
(must rebuild once per *padded* Q — the Q-padding contract), and the
grid itself.  Actuals are compared against the per-backend contract —
``expected_compile_counts(name, plan_devices())``, the
``EXPECTED_COMPILE_COUNTS`` one-device table adjusted for the plan-mesh
device count (device-even padding is a memo-key component, so a larger
mesh legitimately collapses compile classes):

rule ``recompile-churn`` (error)
    More builds than the contract: a varying input leaked into the key
    (or padding was lost), so recurring requests retrace — the §V
    recurring-job amortization story silently dies.

rule ``stale-program`` (error)
    Fewer builds than the contract: a varying input is *missing* from
    the key, so a stale compiled program is silently reused for a
    request it was not built for (jit may mask this by shape-retracing
    under the memo's back, or worse, bake a stale static value).

The expected table itself is emitted (JSON + report) with its hash: a
PR that changes compile-count behaviour moves the hash.

**Static key-coverage check** (``audit_source``).  An AST pass over the
backend sources finds every ``self._program(kind, fn, cluster, extra,
build)`` call site and verifies that each free variable of the
``build`` closure is covered by the memo key: named in the ``extra``
tuple, one of the keyed arguments (fn, cluster, self), a module-level
name, or derived (transitively, through local assignments) from covered
names only.

rule ``unkeyed-static-arg`` (warn)
    A free variable of ``build()`` is not covered — whatever it varies
    with at runtime will not retrace, the exact ``stale-program``
    condition above, caught before it ships.
"""
from __future__ import annotations

import ast
import builtins
import hashlib
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.report import Finding
from repro.core.cluster import ClusterConditions, ResourceDim

_REPO_ROOT = Path(__file__).resolve().parents[3]
_BACKEND_SOURCES = (
    _REPO_ROOT / "src" / "repro" / "core" / "planning_backend.py",
)

PROBES = ("scan_params_reuse", "scan_chunk_churn", "scan_many_qpad",
          "climb_params_reuse", "climb_many_qpad", "grid_rekey",
          "lockstep_wave_qpad")

# The per-backend compile-count contract for the probe battery below,
# at ONE plan-mesh device.  numpy compiles nothing; jax keys chunk
# geometry (so the chunk churn probe legitimately builds twice) and
# builds a stacked climb per padded Q.  With a multi-device plan mesh
# the jax contract SHRINKS (device-even padding collapses chunk/Qpad
# classes) — use ``expected_compile_counts(name, n_devices)``, which
# recomputes the device-dependent probes from the same geometry helpers
# the backends key their program memos on.
EXPECTED_COMPILE_COUNTS: Dict[str, Dict[str, int]] = {
    "numpy": {p: 0 for p in PROBES},
    "jax": {"scan_params_reuse": 1, "scan_chunk_churn": 2,
            "scan_many_qpad": 3, "climb_params_reuse": 1,
            "climb_many_qpad": 2, "grid_rekey": 2,
            "lockstep_wave_qpad": 3},
    "jax_x64": {"scan_params_reuse": 1, "scan_chunk_churn": 2,
                "scan_many_qpad": 3, "climb_params_reuse": 1,
                "climb_many_qpad": 2, "grid_rekey": 2,
                "lockstep_wave_qpad": 3},
}


def plan_devices() -> int:
    """Plan-mesh size the audited backends will shard over (the same
    REPRO_PLAN_DEVICES-capped local device count the backends use); 1
    when jax / the mesh helper is unavailable."""
    try:
        from repro.launch.mesh import plan_device_count
        return plan_device_count()
    except Exception:
        return 1


# probe-battery geometry the device-dependent expectations derive from
# (keep in sync with run_probes / _small_cluster below)
_PROBE_ROWS = 4 * 3                 # _small_cluster grid size
_CHURN_CHUNKS = (8, 4)              # scan_chunk_churn chunk_size sweep
_SCAN_MANY_QS = range(1, 6)         # scan_many_qpad Q sweep
_CLIMB_MANY_QS = range(1, 5)        # climb_many_qpad Q sweep
# lockstep_wave_qpad: two per-query wave sizes, then the stacked
# cross-query union wave (2 + 3 queries' requests in ONE program) — the
# contract that lockstep multi-query stacking introduces no program
# shapes beyond the existing padded-Q classes
_LOCKSTEP_QS = (2, 3, 5)


def expected_compile_counts(backend_name: str,
                            n_devices: int = 1) -> Dict[str, int]:
    """The compile-count contract at ``n_devices`` plan-mesh devices.

    The jax backends key their program memos on sharded-scan geometry —
    per-device chunk ``min(chunk_size, _pad_multiple(total, D) // D)``,
    stacked-scan ``(_pad_even(Q), _many_chunk(...))`` and climb
    ``_pad_multiple(Q, max(2, D))`` — so the expected counts for the
    geometry-sweeping probes are computed from those same helpers rather
    than hard-coded: D == 1 reproduces the legacy literal table, while
    e.g. D == 8 collapses the churn probe's {8, 4} chunk sweep into one
    class (both clip to the 2-row device share of the 12-row grid) and
    the climb Q sweep {1..4} into one padded class of 8.
    """
    base = dict(EXPECTED_COMPILE_COUNTS[backend_name])
    D = max(1, int(n_devices))
    if backend_name not in ("jax", "jax_x64") or D == 1:
        return base
    from repro.core.planning_backend import (DEFAULT_CHUNK, _many_chunk,
                                             _pad_even, _pad_multiple)
    share = _pad_multiple(_PROBE_ROWS, D) // D
    base["scan_chunk_churn"] = len(
        {min(cs, share) for cs in _CHURN_CHUNKS})
    base["scan_many_qpad"] = len(
        {(_pad_even(q), _many_chunk(_PROBE_ROWS, _pad_even(q), D,
                                    DEFAULT_CHUNK))
         for q in _SCAN_MANY_QS})
    base["climb_many_qpad"] = len(
        {_pad_multiple(q, max(2, D)) for q in _CLIMB_MANY_QS})
    base["lockstep_wave_qpad"] = len(
        {(_pad_even(q), _many_chunk(_PROBE_ROWS, _pad_even(q), D,
                                    DEFAULT_CHUNK))
         for q in _LOCKSTEP_QS})
    return base


def _small_cluster() -> ClusterConditions:
    return ClusterConditions(dims=(ResourceDim("a", 1, 4),
                                   ResourceDim("b", 1, 3)))


def _alt_cluster() -> ClusterConditions:
    return ClusterConditions(dims=(ResourceDim("a", 1, 3),
                                   ResourceDim("b", 1, 3)))


def _make_probe_fn():
    """A fresh param-dependent surface per probe: every probe sees a new
    fn object, so the (kind, id(fn), ...) memo keys never alias across
    probes."""
    def probe_fn(cfgs, params):
        c0 = cfgs[:, 0] * 1.0
        c1 = cfgs[:, 1] * 1.0
        return (c0 - params[0]) ** 2 + 0.125 * c1 + params[1] * 0.0
    return probe_fn


def fresh_backend(name: str):
    """A NEW backend instance (never the get_backend singleton: the
    audit must count builds from a cold program memo)."""
    from repro.core.planning_backend import JaxPlanBackend, NumpyPlanBackend
    if name == "numpy":
        return NumpyPlanBackend()
    if name == "jax":
        return JaxPlanBackend()
    if name == "jax_x64":
        return JaxPlanBackend(precision="x64")
    raise ValueError(f"unknown backend {name!r}")


def run_probes(backend) -> Dict[str, int]:
    """Run the probe battery on ``backend``, counting build() calls."""
    counts = {p: 0 for p in PROBES}
    label = {"cur": None}
    if hasattr(backend, "_program"):
        orig = backend._program

        def counting(kind, fn, cluster, extra, build):
            def counted_build():
                counts[label["cur"]] += 1
                return build()
            return orig(kind, fn, cluster, extra, counted_build)

        backend._program = counting

    small, alt = _small_cluster(), _alt_cluster()

    label["cur"] = "scan_params_reuse"
    fn = _make_probe_fn()
    backend.argmin_grid(fn, small, params=np.asarray([1.0, 0.0]))
    backend.argmin_grid(fn, small, params=np.asarray([3.0, 0.0]))

    label["cur"] = "scan_chunk_churn"
    fn = _make_probe_fn()
    backend.argmin_grid(fn, small, params=np.asarray([1.0, 0.0]),
                        chunk_size=8)
    backend.argmin_grid(fn, small, params=np.asarray([1.0, 0.0]),
                        chunk_size=4)

    label["cur"] = "scan_many_qpad"
    fn = _make_probe_fn()
    for q in range(1, 6):                 # Qpad sweeps {2, 4, 6}
        pm = np.stack([[float(i), 0.0] for i in range(1, q + 1)])
        backend.argmin_grid_many(fn, small, pm)

    label["cur"] = "climb_params_reuse"
    fn = _make_probe_fn()
    backend.hill_climb_ensemble(fn, small, params=np.asarray([1.0, 0.0]))
    backend.hill_climb_ensemble(fn, small, params=np.asarray([3.0, 0.0]))

    label["cur"] = "climb_many_qpad"
    fn = _make_probe_fn()
    for q in range(1, 5):                 # Qpad sweeps {2, 4}
        pm = np.stack([[float(i), 0.0] for i in range(1, q + 1)])
        backend.hill_climb_ensemble_many(fn, small, pm)

    label["cur"] = "grid_rekey"
    fn = _make_probe_fn()
    backend.argmin_grid(fn, small, params=np.asarray([1.0, 0.0]))
    backend.argmin_grid(fn, alt, params=np.asarray([1.0, 0.0]))

    label["cur"] = "lockstep_wave_qpad"
    fn = _make_probe_fn()
    for q in _LOCKSTEP_QS:                # per-query waves, then union
        pm = np.stack([[float(i), 0.0] for i in range(1, q + 1)])
        backend.argmin_grid_many(fn, small, pm)

    return counts


def compare_counts(backend_name: str, actual: Dict[str, int],
                   expected: Optional[Dict[str, int]] = None
                   ) -> List[Finding]:
    expected = expected if expected is not None \
        else expected_compile_counts(backend_name, plan_devices())
    src = "src/repro/core/planning_backend.py"
    out: List[Finding] = []
    for probe in PROBES:
        got, want = actual.get(probe, 0), expected.get(probe, 0)
        if got > want:
            out.append(Finding(
                rule="recompile-churn", severity="error", path=src, line=0,
                obj=f"{backend_name}.{probe}",
                message=f"{got} compiles where the contract expects "
                        f"{want}: a runtime-varying input leaked into the "
                        "program memo key (or padding was lost), so "
                        "recurring requests retrace"))
        elif got < want:
            out.append(Finding(
                rule="stale-program", severity="error", path=src, line=0,
                obj=f"{backend_name}.{probe}",
                message=f"{got} compiles where the contract expects "
                        f"{want}: a runtime-varying input is missing from "
                        "the program memo key, so a stale compiled program "
                        "is silently reused"))
    return out


def available_backends() -> List[str]:
    from repro.core.planning_backend import have_backend
    return [n for n in ("numpy", "jax", "jax_x64")
            if have_backend(n)]


def audit_backends(names: Optional[Sequence[str]] = None
                   ) -> Tuple[Dict[str, Dict[str, int]], List[Finding]]:
    """Dynamic sweep over every (available) backend; returns the
    per-backend actual compile-count table plus contract findings."""
    table: Dict[str, Dict[str, int]] = {}
    findings: List[Finding] = []
    for name in (names if names is not None else available_backends()):
        counts = run_probes(fresh_backend(name))
        table[name] = counts
        findings.extend(compare_counts(name, counts))
    return table, findings


def table_hash(table: Dict[str, Dict[str, int]]) -> str:
    """Stable short hash of the compile-count table for trend reports."""
    blob = json.dumps(table, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


# ------------------- static memo-key coverage check ------------------------- #

def _names_in(node: ast.AST) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _value_bases(node: ast.AST) -> set:
    """Names a value expression reads from its scope: loads minus names
    the expression itself binds (comprehension/lambda targets)."""
    loads, stores = set(), set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            (stores if isinstance(n.ctx, ast.Store) else loads).add(n.id)
        elif isinstance(n, ast.Lambda):
            args = n.args
            for a in (args.posonlyargs + args.args + args.kwonlyargs
                      + ([args.vararg] if args.vararg else [])
                      + ([args.kwarg] if args.kwarg else [])):
                stores.add(a.arg)
    return loads - stores


def _bound_names(fn_node: ast.AST) -> set:
    """Names bound inside a function/lambda body (params, assignments,
    loop targets, comprehension targets, nested defs)."""
    bound = set()
    args = fn_node.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        bound.add(a.arg)
    body = fn_node.body if isinstance(fn_node.body, list) else [fn_node.body]
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                bound.add(n.id)
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bound.add(n.name)
                bound.update(_bound_names(n))
            elif isinstance(n, ast.Lambda):
                bound.update(_bound_names(n))
    return bound


def _free_names(fn_node: ast.AST) -> set:
    """Names a function/lambda reads from its enclosing scope.  Default
    value expressions count as free: they capture at build time."""
    bound = _bound_names(fn_node)
    body = fn_node.body if isinstance(fn_node.body, list) else [fn_node.body]
    loads = set()
    for stmt in body:
        loads |= {n.id for n in ast.walk(stmt)
                  if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
    for default in (fn_node.args.defaults + fn_node.args.kw_defaults):
        if default is not None:
            loads |= _names_in(default)
    return loads - bound - set(dir(builtins))


def _module_names(tree: ast.Module) -> set:
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                names.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                names |= {n.id for n in ast.walk(t)
                          if isinstance(n, ast.Name)}
    return names


def _local_derivations(fn_node: ast.AST) -> Dict[str, set]:
    """target name -> base names its assignment reads, for every simple
    assignment / for-target in the function body (nested defs excluded:
    their locals are not this scope's)."""
    deps: Dict[str, set] = {}

    def visit(stmts):
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(stmt, ast.Assign):
                bases = _value_bases(stmt.value)
                for t in stmt.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            deps.setdefault(n.id, set()).update(bases)
            elif isinstance(stmt, ast.AugAssign) and \
                    isinstance(stmt.target, ast.Name):
                deps.setdefault(stmt.target.id, set()).update(
                    _value_bases(stmt.value))
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                bases = _value_bases(stmt.iter)
                for n in ast.walk(stmt.target):
                    if isinstance(n, ast.Name):
                        deps.setdefault(n.id, set()).update(bases)
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, list):
                    continue
            # recurse into compound statement bodies
            for field in ("body", "orelse", "finalbody", "handlers"):
                sub = getattr(stmt, field, None)
                if isinstance(sub, list):
                    visit([s for s in sub if isinstance(s, ast.stmt)])
    visit(fn_node.body)
    return deps


def audit_source(path: Path) -> List[Finding]:
    """Static memo-key coverage for every ``*._program(...)`` call site
    in one source file (see module docstring)."""
    path = Path(path)
    source = path.read_text()
    tree = ast.parse(source)
    try:
        rel = str(path.resolve().relative_to(_REPO_ROOT))
    except ValueError:
        rel = str(path)
    module_names = _module_names(tree)

    # parent function of every node, for enclosing-scope lookup
    enclosing: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            enclosing[child] = node

    def nearest_fn(node):
        cur = enclosing.get(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cur = enclosing.get(cur)
        return cur

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "_program"
                and len(node.args) == 5):
            continue
        _kind, fn_arg, cluster_arg, extra_arg, build_arg = node.args
        covered = (_names_in(extra_arg) | _names_in(fn_arg)
                   | _names_in(cluster_arg) | {"self"}
                   | module_names | set(dir(builtins)))

        scope = nearest_fn(node)
        deps = _local_derivations(scope) if scope is not None else {}
        # fixed point: a local is covered once all its bases are
        changed = True
        while changed:
            changed = False
            for name, bases in deps.items():
                if name not in covered and bases and bases <= covered:
                    covered.add(name)
                    changed = True

        if isinstance(build_arg, ast.Lambda):
            build_node, build_line = build_arg, build_arg.lineno
        elif isinstance(build_arg, ast.Name) and scope is not None:
            defs = [n for n in ast.walk(scope)
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and n.name == build_arg.id]
            if not defs:
                continue
            build_node, build_line = defs[0], defs[0].lineno
        else:
            continue

        qual = scope.name if scope is not None else "<module>"
        for name in sorted(_free_names(build_node) - covered):
            out.append(Finding(
                rule="unkeyed-static-arg", severity="warn", path=rel,
                line=build_line, obj=qual,
                message=f"'{name}' is free in the program build() but not "
                        "covered by the memo-key extra tuple (directly or "
                        "derived from keyed inputs) — runtime variation in "
                        "it silently reuses a stale compiled program"))
    return out


def audit_sources(paths: Sequence[Path] = _BACKEND_SOURCES
                  ) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        if Path(p).exists():
            out.extend(audit_source(p))
    return out
