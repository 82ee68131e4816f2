"""The jax plan-scan programs against the float64 numpy oracle.

``get_backend("jax")`` searches in float32, so these tests use
integer-valued cost tables (exact in f32): its scans (``plan_scan``,
``plan_scan_many_w{Qpad}``) and climbs (``plan_climb``,
``plan_climb_many``) must then agree with numpy *bit-for-bit* — argmin
config, cost, and tie-breaking — on all-infeasible grids, on ties within
and across spans, for stacked scans of any request count, and for a
broker flush against sequential per-operator planning.  The env-lane
tests at the bottom run the same parity properties against whichever
backend the CI matrix selected via ``REPRO_PLAN_BACKEND`` (see
tests/conftest.py).
"""
import math
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cluster import ClusterConditions, ResourceDim, paper_cluster
from repro.core.cost_model import simulator_cost_models
from repro.core.plan_broker import PlanBroker
from repro.core.planning_backend import get_backend
from repro.core.plans import OperatorCosting

try:
    import jax  # noqa: F401
    import jax.numpy as jnp
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")


# ----------------------- random grid helpers ------------------------------- #

def _random_cluster(rng, na: int, nb: int, ragged: bool):
    """Two-dim cluster; optionally a ragged-stepped dim plus an
    explicit-values dim, exercising both decode paths (affine arithmetic
    and compare-select over the value table)."""
    if ragged:
        step = int(rng.integers(2, 4))
        hi = 1 + step * (na - 1) + int(rng.integers(1, step))
        da = ResourceDim("a", 1, hi, step=step)
        vals = tuple(sorted(rng.choice(np.arange(1, 64), size=nb,
                                       replace=False).tolist()))
        db = ResourceDim("b", int(vals[0]), int(vals[-1]), values=vals)
    else:
        da = ResourceDim("a", 0, na - 1)
        db = ResourceDim("b", 0, nb - 1)
    return ClusterConditions(dims=(da, db))


def _table_fn(cluster, table, xp):
    """Batch cost fn looking up an (na, nb) table by config value.
    Integer-valued costs are exact in float32, so f32 backends must agree
    with numpy exactly, ties included."""
    ga, gb = (np.asarray(d.grid(), dtype=np.int64) for d in cluster.dims)
    t = xp.asarray(table)
    ga_x, gb_x = xp.asarray(ga), xp.asarray(gb)

    def fn(cfgs, params=None):
        a = xp.asarray(cfgs)
        i = xp.searchsorted(ga_x, a[:, 0])
        j = xp.searchsorted(gb_x, a[:, 1])
        return t[i, j]
    return fn


def _random_table(rng, na, nb, oom_frac=0.15):
    table = rng.integers(0, 1 << 20, size=(na, nb)).astype(np.float64)
    table[rng.random((na, nb)) < oom_frac] = np.inf   # OOM-masked cells
    return table


def _assert_same(a, b):
    (ra, ca), (rb, cb) = a, b
    assert ra == rb
    assert (ca == cb) or (math.isinf(ca) and math.isinf(cb))


def _param_fn(xp):
    """Cost surface that depends on per-request params (integer-exact):
    cost = table-free arithmetic of config and a per-request offset."""
    def fn(cfgs, params):
        a = xp.asarray(cfgs)
        base = (a[:, 0] * 37 + a[:, 1] * 11) % 101
        return base * 8.0 + params[0]
    return fn


# ------------------------------ the jax scans ------------------------------ #

@needs_jax
@pytest.mark.parametrize("method", ["argmin_grid", "argmin_grid_many"])
def test_all_infeasible_grid_returns_none(method):
    """A grid on which every configuration costs inf has no plan: one
    span (the plain body) answers ``(None, inf)``, per request."""
    cluster = paper_cluster(7, 5)
    table = np.full((7, 5), np.inf)
    be = get_backend("jax")
    fn = _table_fn(cluster, table, jnp)
    if method == "argmin_grid":
        got = [be.argmin_grid(fn, cluster)]
    else:
        got = be.argmin_grid_many(fn, cluster, np.zeros((3, 1)))
    assert got == [(None, math.inf)] * len(got)


@needs_jax
@pytest.mark.parametrize("chunk_size", [60, 13, 7, 1])
def test_tie_break_index_identity(chunk_size):
    """Duplicated minima resolve to the FIRST config in
    ``enumerate_configs`` order, exactly like the numpy backend, whether
    they share a span (one span of 60 rows) or lie in different spans
    that the device loop merges (spans of 13, 7 and 1 rows)."""
    cluster = ClusterConditions(dims=(ResourceDim("a", 0, 11),
                                      ResourceDim("b", 0, 4)))
    table = np.full((12, 5), 9.0)
    table[3, 2] = table[7, 1] = table[7, 3] = 1.0   # three tied minima
    be = get_backend("jax")
    r_np = get_backend("numpy").argmin_grid(_table_fn(cluster, table, np),
                                            cluster)
    assert r_np[0] == (3, 2)                        # first in scan order
    _assert_same(be.argmin_grid(_table_fn(cluster, table, jnp), cluster,
                                chunk_size=chunk_size), r_np)
    # constant surface: every config ties -> the very first config wins
    flat = np.zeros((12, 5))
    r_c = get_backend("numpy").argmin_grid(_table_fn(cluster, flat, np),
                                           cluster)
    assert r_c[0] == (0, 0)
    _assert_same(be.argmin_grid(_table_fn(cluster, flat, jnp), cluster,
                                chunk_size=chunk_size), r_c)


@needs_jax
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), q=st.integers(1, 6),
       ragged=st.booleans())
def test_hypothesis_stacked_scan_equals_sequential(seed, q, ragged):
    """A (Q, P)-stacked jax scan == Q sequential jax scans == Q numpy
    scans, for odd Q (padded to even with an edge copy) and even Q."""
    rng = np.random.default_rng(seed)
    cluster = _random_cluster(rng, int(rng.integers(3, 10)),
                              int(rng.integers(3, 8)), ragged)
    pm = rng.integers(0, 1000, size=(q, 1)).astype(np.float64)
    be = get_backend("jax")
    ref = [get_backend("numpy").argmin_grid(_param_fn(np), cluster,
                                            params=pm[i])
           for i in range(q)]
    got = be.argmin_grid_many(_param_fn(jnp), cluster, pm)
    seq = [be.argmin_grid(_param_fn(jnp), cluster, params=pm[i])
           for i in range(q)]
    assert len(got) == q
    for g, s, r in zip(got, seq, ref):
        _assert_same(g, s)
        _assert_same(g, r)


@needs_jax
def test_stacked_scan_chunks_large_q(monkeypatch):
    """A wide stack (41 requests, padded to 42) shrinks the span to keep
    its live cost block under ``MAX_LIVE_ELEMENTS``, so the scan loops
    over several spans in one launch, with unchanged results."""
    from repro.core import planning_backend as pb
    from repro.obs import get_metrics
    q = 41
    monkeypatch.setattr(pb, "MAX_LIVE_ELEMENTS", 42 * 8)    # 8-row spans
    cluster = paper_cluster(9, 4)
    pm = np.arange(q, dtype=np.float64).reshape(q, 1) * 3.0
    mx = get_metrics()

    def counts():
        snap = mx.snapshot()
        return (snap.get("backend.launches", 0),
                snap.get("backend.scan_spans", 0))
    before = counts()
    got = get_backend("jax").argmin_grid_many(_param_fn(jnp), cluster, pm)
    after = counts()
    ref = get_backend("numpy").argmin_grid_many(_param_fn(np), cluster, pm)
    # one launch sweeping 36 rows in spans of 8
    assert (after[0] - before[0], after[1] - before[1]) == (1, 5)
    assert len(got) == q
    for g, r in zip(got, ref):
        _assert_same(g, r)


@needs_jax
def test_ensemble_many_equals_per_request():
    """The stacked climb (``plan_climb_many``, one vmapped program) gives
    each request the climb it gets alone, and numpy's."""
    cluster = paper_cluster(12, 6)
    pm = np.asarray([[5.0], [250.0], [777.0]])
    be = get_backend("jax")
    many = be.hill_climb_ensemble_many(_param_fn(jnp), cluster, pm,
                                       n_random=4, seed=1)
    seq = [be.hill_climb_ensemble(_param_fn(jnp), cluster, params=pm[i],
                                  n_random=4, seed=1) for i in range(3)]
    ref = get_backend("numpy").hill_climb_ensemble_many(
        _param_fn(np), cluster, pm, n_random=4, seed=1)
    assert many == seq == ref


# --------------------------- broker flush on jax ---------------------------- #

@needs_jax
@pytest.mark.parametrize("mode", ["batched", "ensemble"])
def test_broker_flush_jax_identical_with_sequential(mode):
    """A PlanBroker("jax") flush (stacked programs) must return exactly
    the plans and costs of sequential per-operator planning on the same
    backend (winners re-committed through scalar float64 on both
    ends)."""
    kw = dict(models=simulator_cost_models(), cluster=paper_cluster(40, 10),
              resource_planning=mode)
    seq = OperatorCosting(backend="jax", **kw)
    brk = OperatorCosting(broker=PlanBroker("jax"), **kw)
    ops = [("SMJ", 2.0, 74.0), ("BHJ", 1.0, 74.0), ("SMJ", 3.0, 50.0),
           ("BHJ", 0.5, 20.0), ("SMJ", 2.0, 74.0)]    # recurring op
    for op in ops:
        brk.prefetch(*op)
    assert [brk.plan_resources(*op) for op in ops] == \
        [seq.plan_resources(*op) for op in ops]


@needs_jax
def test_jax_backend_protocol_surface():
    """``get_backend("jax")``: a process-wide float32 singleton that the
    broker drives through both async splits, each a dispatch that returns
    the finalize closure giving the synchronous answer."""
    be = get_backend("jax")
    assert be is get_backend("jax") is get_backend("auto")
    assert be.name == "jax" and be.exact is False
    assert be.precision == "float32"
    assert be.xp is jnp
    cluster = paper_cluster(6, 4)
    pm = np.asarray([[2.0], [9.0], [4.0]])
    fin = be.argmin_grid_many_async(_param_fn(jnp), cluster, pm)
    assert callable(fin)
    assert fin() == be.argmin_grid_many(_param_fn(jnp), cluster, pm)
    fin = be.hill_climb_ensemble_many_async(_param_fn(jnp), cluster, pm,
                                            n_random=3, seed=2)
    assert callable(fin)
    assert fin() == be.hill_climb_ensemble_many(_param_fn(jnp), cluster, pm,
                                                n_random=3, seed=2)


def test_unknown_backend_names_the_ones_that_exist():
    """A name outside numpy, jax, jax_x64 and auto is refused, and the
    refusal lists exactly those four."""
    with pytest.raises(ValueError) as err:
        get_backend("fused")
    msg = str(err.value)
    assert "'fused'" in msg
    assert msg.endswith("(expected 'numpy', 'jax', 'jax_x64' or 'auto')")


# ------------------- env-selected backend lane (CI matrix) ------------------ #
# The same parity properties, run against whatever REPRO_PLAN_BACKEND the
# CI matrix selected (numpy lane degenerates to oracle == oracle).  The
# non-hypothesis tests take the conftest ``plan_backend`` fixture; the
# hypothesis one reads the env directly because the in-repo hypothesis
# fallback's @given wrapper cannot request pytest fixtures.

_ENV_BACKEND = os.environ.get("REPRO_PLAN_BACKEND", "").strip() or "numpy"


def _env_backend():
    try:
        return get_backend(_ENV_BACKEND)
    except ImportError:
        pytest.skip(f"backend {_ENV_BACKEND!r} needs jax, "
                    "which is not installed")


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), na=st.integers(2, 10),
       nb=st.integers(2, 8), ragged=st.booleans())
def test_hypothesis_env_backend_argmin_matches_numpy(seed, na, nb, ragged):
    be = _env_backend()
    rng = np.random.default_rng(seed)
    cluster = _random_cluster(rng, na, nb, ragged)
    table = _random_table(rng, na, nb)
    _assert_same(
        be.argmin_grid(_table_fn(cluster, table, be.xp), cluster),
        get_backend("numpy").argmin_grid(_table_fn(cluster, table, np),
                                         cluster))


def test_env_backend_stacked_scan_matches_numpy(plan_backend):
    cluster = paper_cluster(11, 5)
    pm = np.asarray([[3.0], [407.0], [21.0], [998.0]])
    got = plan_backend.argmin_grid_many(_param_fn(plan_backend.xp),
                                        cluster, pm)
    ref = get_backend("numpy").argmin_grid_many(_param_fn(np), cluster, pm)
    for g, r in zip(got, ref):
        _assert_same(g, r)


@pytest.mark.parametrize("mode", ["batched", "ensemble"])
def test_env_backend_broker_flush_matches_sequential(plan_backend_name,
                                                     plan_backend, mode):
    kw = dict(models=simulator_cost_models(), cluster=paper_cluster(35, 9),
              resource_planning=mode)
    seq = OperatorCosting(backend=plan_backend_name, **kw)
    brk = OperatorCosting(broker=PlanBroker(plan_backend_name), **kw)
    ops = [("SMJ", 1.5, 60.0), ("BHJ", 0.8, 60.0), ("SMJ", 4.0, 120.0)]
    for op in ops:
        brk.prefetch(*op)
    assert [brk.plan_resources(*op) for op in ops] == \
        [seq.plan_resources(*op) for op in ops]
