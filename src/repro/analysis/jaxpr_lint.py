"""Pass 1 — jaxpr contract lint for registered cost surfaces.

Traces every registered batch cost surface (``repro.analysis.registry``)
to a jaxpr exactly the way ``JaxPlanBackend`` would, and checks the
machine-checkable invariants the backends depend on:

rule ``tracer-bool`` (error)
    Tracing raised a concretization error: the surface branches on (or
    converts) traced values in Python.  Data-dependent Python control
    flow silently specializes — or, as here, refuses to trace — inside
    jitted search programs; use ``xp.where`` masks instead.

rule ``dtype`` (error)
    The cost output is not a single float vector over the config axis,
    or a float16/bfloat16 cast appears on the argmin path.  Low-precision
    intermediates can flip a strict-``<`` winner that the float64 commit
    then rejects, reintroducing the parity-fallback churn the exact
    backends exist to remove.

rule ``weak-type`` (warn)
    The cost output is weakly typed.  A weak result re-promotes against
    whatever it later meets, so otherwise-identical traces stop being
    cache-identical — the program-memo churn class.  Anchor the dtype
    (e.g. multiply by ``xp.asarray(1.0)`` or cast explicitly).

rule ``closure-capture`` (warn)
    A 0-d array captured from the enclosing scope became a jaxpr const:
    that is a per-request scalar baked into the compiled program (a new
    value means a full retrace).  Per-request scalars belong in
    ``params``.

rule ``cross-config-reduce`` (error)
    A reduction runs across the config axis.  Costs must be elementwise
    per configuration: the chunked scans evaluate the surface on
    *slices* of the grid, so any cross-config coupling makes the result
    depend on chunk geometry and breaks the strict-``<`` first-minimum
    contract between backends.

Python/numpy scalar captures fold into jaxpr *literals* (not consts) and
are indistinguishable from legitimate model coefficients, so only array
captures are detectable.
"""
from __future__ import annotations

import inspect
from pathlib import Path
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.analysis.registry import CostSurface, iter_cost_surfaces
from repro.analysis.report import Finding

# distinctive config-axis length for trace probes: reductions over an
# axis of this size are reductions over configs (no shipped surface has
# another axis of 7)
TRACE_ROWS = 7

LOW_PRECISION = ("float16", "bfloat16")
REDUCE_PRIMS = {"reduce_min", "reduce_max", "reduce_sum", "reduce_prod",
                "reduce_and", "reduce_or", "argmin", "argmax",
                "cumsum", "cummax", "cummin", "cumprod", "sort"}

_REPO_ROOT = Path(__file__).resolve().parents[3]


def _locate(fn: Callable) -> tuple:
    """(repo-relative path, def line) of a callable, best effort."""
    try:
        target = inspect.unwrap(fn)
        path = inspect.getsourcefile(target)
        line = target.__code__.co_firstlineno
    except (TypeError, OSError, AttributeError):
        return "<unknown>", 0
    if path is None:
        return "<unknown>", 0
    p = Path(path).resolve()
    try:
        return str(p.relative_to(_REPO_ROOT)), line
    except ValueError:
        return str(p), line


def _iter_eqns(jaxpr):
    """All equations, descending into sub-jaxprs (scan/while/cond/pjit)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                yield from _iter_eqns(sub)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    sub = getattr(item, "jaxpr", None)
                    if sub is not None:
                        yield from _iter_eqns(sub)


def lint_cost_fn(fn: Callable, n_dims: int, p_width: int, *,
                 name: str, n_rows: int = TRACE_ROWS) -> List[Finding]:
    """Trace one param-style batch cost fn and check the contracts."""
    import jax
    import jax.numpy as jnp

    path, line = _locate(fn)

    def finding(rule, severity, message):
        return Finding(rule=rule, severity=severity, path=path, line=line,
                       obj=name, message=message)

    cfgs_ex = jax.ShapeDtypeStruct((n_rows, n_dims), jnp.int32)
    p_ex = jax.ShapeDtypeStruct((max(1, p_width),), jnp.float32)
    try:
        closed = jax.make_jaxpr(lambda c, p: fn(c, p))(cfgs_ex, p_ex)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError) as e:
        first = str(e).strip().splitlines()[0]
        return [finding(
            "tracer-bool", "error",
            "data-dependent Python control flow or host conversion while "
            f"tracing ({type(e).__name__}: {first}) — use xp.where masks; "
            "the surface cannot run inside the jitted scans")]
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        first = str(e).strip().splitlines()[0]
        return [finding(
            "tracer-bool", "error",
            f"tracing failed ({type(e).__name__}: {first}) — the surface "
            "is not traceable with xp=jax.numpy")]

    out: List[Finding] = []
    jaxpr = closed.jaxpr

    # ---- output contract ----------------------------------------------- #
    if len(jaxpr.outvars) != 1:
        out.append(finding(
            "dtype", "error",
            f"cost surface returned {len(jaxpr.outvars)} outputs; the "
            "backends require exactly one (n_configs,) cost vector"))
    else:
        aval = jaxpr.outvars[0].aval
        if tuple(aval.shape) != (n_rows,):
            out.append(finding(
                "dtype", "error",
                f"cost output has shape {tuple(aval.shape)} for "
                f"({n_rows}, {n_dims}) configs; expected ({n_rows},) — "
                "one cost per configuration"))
        if not np.issubdtype(aval.dtype, np.floating):
            out.append(finding(
                "dtype", "error",
                f"cost output dtype is {aval.dtype}, not float — argmin "
                "selection and the inf infeasibility mask require a float "
                "cost vector"))
        elif getattr(aval, "weak_type", False):
            out.append(finding(
                "weak-type", "warn",
                "cost output is weakly typed: weak results re-promote per "
                "call context, so otherwise-identical traces churn the "
                "compiled-program memo — anchor the dtype explicitly"))

    # ---- primitive scan -------------------------------------------------- #
    for eqn in _iter_eqns(jaxpr):
        pname = eqn.primitive.name
        if pname == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            if new in LOW_PRECISION:
                out.append(finding(
                    "dtype", "error",
                    f"{new} cast on the argmin path: low-precision "
                    "intermediates can flip a strict-< winner that the "
                    "float64 commit then rejects"))
        if pname in REDUCE_PRIMS:
            axes = eqn.params.get("axes", eqn.params.get("axis", ()))
            if isinstance(axes, int):
                axes = (axes,)
            for operand in eqn.invars:
                shape = tuple(getattr(operand.aval, "shape", ()))
                if any(0 <= ax < len(shape) and shape[ax] == n_rows
                       for ax in (axes or ())):
                    out.append(finding(
                        "cross-config-reduce", "error",
                        f"{pname} reduces across the config axis: costs "
                        "must be elementwise per configuration, or chunked "
                        "/ blocked scans change the result with the chunk "
                        "geometry"))
                    break

    # ---- closure consts --------------------------------------------------- #
    for const in closed.consts:
        if np.ndim(const) == 0:
            out.append(finding(
                "closure-capture", "warn",
                f"0-d {np.result_type(const)} array captured from the "
                "enclosing scope is baked into the traced program (a new "
                "value means a full retrace) — per-request scalars belong "
                "in params"))
    return out


def lint_surface(surface: CostSurface) -> List[Finding]:
    import jax.numpy as jnp
    try:
        fn = surface.make_fn(jnp)
        cluster = surface.make_cluster()
    except Exception as e:  # noqa: BLE001 — a broken factory is a finding
        return [Finding(
            rule="tracer-bool", severity="error", path="<registry>", line=0,
            obj=surface.name,
            message=f"surface factory failed: {type(e).__name__}: {e}")]
    return lint_cost_fn(fn, cluster.n_dims, len(surface.params),
                        name=surface.name)


def lint_registered(domain: Optional[str] = None,
                    names: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint every registered cost surface (importing the modules that
    register the shipped ones)."""
    import repro.core.cost_model    # noqa: F401 — registers DB surfaces
    import repro.core.roofline      # noqa: F401 — registers TPU surfaces
    out: List[Finding] = []
    for s in iter_cost_surfaces(domain):
        if names is not None and s.name not in names:
            continue
        out.extend(lint_surface(s))
    return out
