"""Pass 3 — AST host-sync lint over designated hot paths.

Hot paths are functions decorated ``@hot_path("reason")``
(``repro.analysis.registry``): the broker flush machinery and the
jitted backend's per-request scan/climb drivers.  Inside them —
including nested ``def``s — the following calls force a device->host
synchronization and are flagged:

    float(x)            .item()             np.asarray(x)
    jax.device_get(x)   x.block_until_ready()

rule ``host-sync``
    * **warn** when the call sits inside a ``for``/``while`` loop of the
      hot function: a sync per chunk/iteration serializes the async
      dispatch pipeline (the exact bug class the single-sync
      ``argmin_grid_many`` rewrite removed).
    * **info** at loop depth zero: one deliberate sync per call is the
      documented pattern (fold once at the end); it stays visible in the
      report without failing ``--fail-on warn``.

**Host-value tracking.**  A name assigned from an expression containing
``np.asarray(...)`` (or aliased from such a name) holds a *numpy* array:
the device->host transfer already happened at the asarray.  Subsequent
``float(x)`` / ``x.item()`` on these names — e.g. the per-request decode
loop reading a synced ``(C, Q)`` cost matrix — are free and NOT flagged,
so the fold-once-then-decode pattern needs no pragmas.  The asarray call
itself is still the flagged sync.

rule ``sync-budget``
    ``@hot_path(..., folds=N)`` declares the function's depth-zero
    host-sync budget: the documented end-of-scan fold sites.  When the
    visitor finds MORE depth-zero syncs than declared, a **warn** fires
    at the function head — the cross-shard fold must stay the single
    (well, declared) synchronization point, and new un-budgeted syncs
    are exactly how overlap regressions sneak in.

**Obs calls are sync-free.**  The tracing/metrics layer (``repro.obs``)
records monotonic clocks only — it never reads a device value — so span
and metric calls rooted at the conventional singleton bindings
(``_obs`` / ``_metrics`` / ``get_tracer()`` / ``get_metrics()``, plus
``with _obs.span(...) as sp`` aliases) are skipped entirely, arguments
included: ``_obs.instant("tick", cost=float(c))`` in a hot loop is
attribution payload on a host value, not a device sync, and needs no
``allow(host-sync)`` pragma.  Instrumented hot paths therefore lint
clean by construction (golden fixture in tests/fixtures_plan_lint.py).

Suppressions use the inline pragma — ``# plan-lint:`` then
``allow(host-sync): reason`` — on the offending line or the line above;
a pragma without a reason is a ``pragma-no-reason`` warning (report.py).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterator, List, Optional, Set, Tuple

from repro.analysis.report import (Finding, apply_pragmas, pragma_findings)

SYNC_ATTRS = {"item", "block_until_ready", "device_get"}
NP_MODULE_NAMES = {"np", "numpy"}

# obs (repro.obs) span/metric calls are sync-free by contract: the
# tracer reads monotonic clocks only and never touches device values, so
# anything inside an obs call's argument list is attribution payload on
# already-host values, not a device sync.  Roots are deliberately the
# UNAMBIGUOUS conventional bindings only (`_obs = get_tracer()` /
# `_metrics = get_metrics()`) and the accessors themselves — a stray
# variable merely named `metrics` never earns the exemption; `with ...
# as sp:` / `sp = _obs.span(...)` aliases are tracked per function like
# host names
OBS_ROOT_NAMES = {"_obs", "_tracer", "_metrics",
                  "get_tracer", "get_metrics"}

_REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_TREE = _REPO_ROOT / "src" / "repro"


def _is_hot_decorator(dec: ast.expr) -> bool:
    """``@hot_path("...")`` — possibly attribute-qualified."""
    if isinstance(dec, ast.Call):
        dec = dec.func
    if isinstance(dec, ast.Name):
        return dec.id == "hot_path"
    if isinstance(dec, ast.Attribute):
        return dec.attr == "hot_path"
    return False


def _sync_call(node: ast.Call) -> str:
    """Non-empty description when the call is a known host sync."""
    fn = node.func
    if isinstance(fn, ast.Name) and fn.id == "float":
        return "float() on a device value"
    if isinstance(fn, ast.Attribute):
        if fn.attr == "asarray" and isinstance(fn.value, ast.Name) \
                and fn.value.id in NP_MODULE_NAMES:
            return "np.asarray() materializes on host"
        if fn.attr == "item":
            return ".item() pulls a scalar to host"
        if fn.attr == "block_until_ready":
            return ".block_until_ready() blocks on the device"
        if fn.attr == "device_get":
            return "jax.device_get() transfers to host"
    return ""


def _is_np_asarray(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "asarray"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in NP_MODULE_NAMES)


def _root_name(node: ast.AST) -> Optional[str]:
    """The base Name of a subscript/attribute chain (``costs[k[q], q]``
    -> ``costs``), or None."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _call_chain(node: ast.Call) -> List[str]:
    """Dotted/called segments of a call's func, outermost attr first:
    ``self._obs.span(...)`` -> ["span", "_obs", "self"];
    ``get_metrics().histogram("h").observe(x)`` -> ["observe",
    "histogram", "get_metrics"]."""
    parts: List[str] = []
    fn = node.func
    while True:
        if isinstance(fn, ast.Attribute):
            parts.append(fn.attr)
            fn = fn.value
        elif isinstance(fn, ast.Call):
            fn = fn.func
        else:
            break
    if isinstance(fn, ast.Name):
        parts.append(fn.id)
    return parts


class _HotFnVisitor(ast.NodeVisitor):
    """Walk one hot function (nested defs included), tracking loop depth
    and which names hold already-synced host (numpy) values."""

    def __init__(self, path: str, qualname: str, reason: str):
        self.path = path
        self.qualname = qualname
        self.reason = reason
        self.loop_depth = 0
        self.host_names: Set[str] = set()
        self.obs_names: Set[str] = set(OBS_ROOT_NAMES)
        self.findings: List[Finding] = []

    def _is_obs_call(self, node: ast.Call) -> bool:
        """A span/metric call on an obs root (or a tracked span alias):
        sync-free by contract, arguments included."""
        parts = _call_chain(node)
        return len(parts) >= 2 and \
            any(p in self.obs_names for p in parts[1:])

    def _loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _loop

    def _is_hosty(self, expr: ast.AST) -> bool:
        """The expression yields a host (numpy) value: it contains an
        ``np.asarray`` call, or roots in an already-tracked name."""
        if any(_is_np_asarray(n) for n in ast.walk(expr)):
            return True
        root = _root_name(expr)
        return root is not None and root in self.host_names

    def visit_Assign(self, node: ast.Assign):
        if self._is_hosty(node.value):
            for tgt in node.targets:
                elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                self.host_names.update(
                    e.id for e in elts if isinstance(e, ast.Name))
        if isinstance(node.value, ast.Call) \
                and self._is_obs_call(node.value):
            # `sp = _obs.span(...)`: alias the span handle
            self.obs_names.update(
                t.id for t in node.targets if isinstance(t, ast.Name))
        self.generic_visit(node)

    def visit_With(self, node):
        # `with _obs.span(...) as sp:` — sp.set(...) payload is obs too
        for item in node.items:
            if isinstance(item.context_expr, ast.Call) \
                    and self._is_obs_call(item.context_expr) \
                    and isinstance(item.optional_vars, ast.Name):
                self.obs_names.add(item.optional_vars.id)
        self.generic_visit(node)

    visit_AsyncWith = visit_With

    def visit_Call(self, node: ast.Call):
        if self._is_obs_call(node):
            # do not recurse: host conversions in the argument list are
            # attribution payload, not device syncs (module docstring)
            return
        desc = _sync_call(node)
        if desc:
            # float()/.item() on a tracked host name is not a device
            # sync — the transfer happened at the asarray that fed it
            arg = None
            if isinstance(node.func, ast.Name) and node.args:
                arg = node.args[0]                 # float(x)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item":
                arg = node.func.value              # x.item()
            if arg is not None and not _is_np_asarray(node):
                root = _root_name(arg)
                if root is not None and root in self.host_names:
                    self.generic_visit(node)
                    return
            in_loop = self.loop_depth > 0
            self.findings.append(Finding(
                rule="host-sync",
                severity="warn" if in_loop else "info",
                path=self.path, line=node.lineno, obj=self.qualname,
                message=desc + (
                    " inside a loop of a hot path — one sync per "
                    "iteration serializes the async dispatch pipeline"
                    if in_loop else
                    " in a hot path (single deliberate sync)")))
        self.generic_visit(node)


def _iter_hot_functions(tree: ast.Module
                        ) -> Iterator[Tuple[ast.AST, str, str,
                                            Optional[int]]]:
    """(function node, qualname, reason, declared folds budget) for
    every @hot_path def."""
    stack: List[Tuple[ast.AST, str]] = [(tree, "")]
    while stack:
        node, prefix = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                hot = [d for d in child.decorator_list
                       if _is_hot_decorator(d)]
                if hot:
                    reason, folds = "", None
                    d = hot[0]
                    if isinstance(d, ast.Call):
                        if d.args and isinstance(d.args[0], ast.Constant):
                            reason = str(d.args[0].value)
                        for kw in d.keywords:
                            if kw.arg == "folds" and \
                                    isinstance(kw.value, ast.Constant) \
                                    and isinstance(kw.value.value, int):
                                folds = kw.value.value
                    yield child, qual, reason, folds
                else:
                    # nested defs of a hot fn are covered by its visitor;
                    # only recurse into *non*-hot scopes looking for more
                    stack.append((child, qual + "."))
            elif isinstance(child, ast.ClassDef):
                stack.append((child, f"{prefix}{child.name}."))


def lint_file(path: Path) -> List[Finding]:
    """Hot-path host-sync findings (+ pragma hygiene) for one file,
    with pragmas already applied."""
    path = Path(path)
    source = path.read_text()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(rule="host-sync", severity="error",
                        path=_rel(path), line=e.lineno or 0, obj="<module>",
                        message=f"file does not parse: {e.msg}")]
    rel = _rel(path)
    findings: List[Finding] = []
    for fn_node, qual, reason, folds in _iter_hot_functions(tree):
        v = _HotFnVisitor(rel, qual, reason)
        # visit the body (not the def itself, so decorators are skipped)
        for stmt in fn_node.body:
            v.visit(stmt)
        findings.extend(v.findings)
        if folds is not None:
            depth0 = sum(1 for f in v.findings if f.severity == "info")
            if depth0 > folds:
                findings.append(Finding(
                    rule="sync-budget", severity="warn", path=rel,
                    line=fn_node.lineno, obj=qual,
                    message=(f"{depth0} depth-zero host syncs exceed the "
                             f"declared folds={folds} budget — the "
                             "cross-shard fold must stay the declared "
                             "synchronization point (raise folds only "
                             "with the design note to match)")))
    findings.extend(pragma_findings(rel, source))
    return apply_pragmas(findings, {rel: source})


def lint_tree(root: Path = DEFAULT_TREE) -> List[Finding]:
    out: List[Finding] = []
    for path in sorted(Path(root).rglob("*.py")):
        out.extend(lint_file(path))
    return out


def _rel(path: Path) -> str:
    p = Path(path).resolve()
    try:
        return str(p.relative_to(_REPO_ROOT))
    except ValueError:
        return str(p)
