"""Backend parity: numpy and jax PlanBackends (and scalar terms_for vs
batched terms_grid) must agree — same argmin configs on random grids
(OOM-masked and ragged-stepped included), bit-identical numpy roofline
grids, and identical vectorized ShardingPlanner plans vs the scalar
search path."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, get_shape
from repro.core.cluster import (ClusterConditions, ResourceDim, as_configs,
                                paper_cluster, scaled_cluster)
from repro.core.cost_model import simulator_cost_models
from repro.core.hillclimb import brute_force, hill_climb_multi
from repro.core.planning_backend import (enumerate_configs, get_backend,
                                         start_indices)
from repro.core.plans import OperatorCosting
from repro.core.roofline import Resources, terms_for, terms_grid
from repro.core.sharding_planner import (PLAN_CHOICES, ShardingPlanner,
                                         TpuCluster)

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

ARCHS = ("deepseek-67b", "qwen3-moe-30b-a3b", "falcon-mamba-7b",
         "zamba2-2.7b")
SHAPES = ("train_4k", "prefill_32k", "decode_32k")


# ------------------- random grid helpers (ragged + OOM) -------------------- #

def _random_cluster(rng, na: int, nb: int, ragged: bool):
    """Two-dim cluster; optionally a ragged step dim ((hi-lo) % step != 0)
    and an explicit-values dim, exercising both grid encodings."""
    if ragged:
        step = int(rng.integers(2, 4))
        hi = 1 + step * (na - 1) + int(rng.integers(1, step))  # ragged top
        da = ResourceDim("a", 1, hi, step=step)
        vals = tuple(sorted(rng.choice(np.arange(1, 64), size=nb,
                                       replace=False).tolist()))
        db = ResourceDim("b", int(vals[0]), int(vals[-1]), values=vals)
    else:
        da = ResourceDim("a", 0, na - 1)
        db = ResourceDim("b", 0, nb - 1)
    return ClusterConditions(dims=(da, db))


def _table_fn(cluster, table, xp):
    """Batch cost fn looking up an (na, nb) table by config value; written
    with xp ops so it is jax-traceable.  Integer-valued costs are exact in
    float32, so numpy and jax argmins match exactly, ties included."""
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


# ------------------------- argmin-grid parity ------------------------------ #

@needs_jax
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), na=st.integers(2, 12),
       nb=st.integers(2, 9), ragged=st.booleans())
def test_hypothesis_jax_numpy_argmin_identical(seed, na, nb, ragged):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    cluster = _random_cluster(rng, na, nb, ragged)
    table = _random_table(rng, na, nb)
    r_np, c_np = get_backend("numpy").argmin_grid(
        _table_fn(cluster, table, np), cluster)
    r_jx, c_jx = get_backend("jax").argmin_grid(
        _table_fn(cluster, table, jnp), cluster)
    assert r_jx == r_np
    assert (c_jx == c_np) or (math.isinf(c_jx) and math.isinf(c_np))


# A range grid with lo != 1 and step > 1, and a grid with one explicit-value
# dim; neither size (1240, 1050) is a multiple of the 512-row chunk, so the
# last span of each scan is masked.
DECODE_GRIDS = {
    "range": ClusterConditions(dims=(ResourceDim("a", 3, 159, step=4),
                                     ResourceDim("b", 2, 92, step=3))),
    "mixed": ClusterConditions(dims=(
        ResourceDim("a", 5, 303, step=2),
        ResourceDim("b", 1, 64, values=(1, 2, 4, 8, 16, 32, 64)))),
}


def _table_param_fn(cluster, table, xp):
    """``table[config] + params[0]``: integer-valued (exact in float32,
    many ties), read through ``as_configs`` as the cost surfaces do.  A
    config off the grid costs -inf, so a wrong decode wins the scan."""
    ga, gb = (xp.asarray(np.asarray(d.grid(), dtype=np.int64))
              for d in cluster.dims)
    t = xp.asarray(table)

    def fn(cfgs, params=None):
        a = as_configs(cfgs, xp)
        i = xp.minimum(xp.searchsorted(ga, a[:, 0]), len(ga) - 1)
        j = xp.minimum(xp.searchsorted(gb, a[:, 1]), len(gb) - 1)
        on_grid = (ga[i] == a[:, 0]) & (gb[j] == a[:, 1])
        c = xp.where(on_grid, t[i, j], -xp.inf)
        return c if params is None else c + params[0]
    return fn


def _target_fn(xp):
    """Squared distance to the config ``params``: 0 at that grid point
    alone, so a request finds its target only where it decodes right."""
    def fn(cfgs, params):
        a = as_configs(cfgs, xp)
        return (a[:, 0] - params[0]) ** 2 + (a[:, 1] - params[1]) ** 2
    return fn


@needs_jax
@pytest.mark.parametrize("method", ["argmin_grid", "argmin_grid_many"])
@pytest.mark.parametrize("grid", sorted(DECODE_GRIDS))
@pytest.mark.parametrize("backend", ["jax", "jax_x64"])
def test_jax_decode_matches_numpy_oracle(backend, grid, method):
    """The jax scans' arithmetic decode (affine dims by ``lo + step *
    idx``, value-table dims by compare-select) returns the numpy oracle's
    configs and costs, ties and the masked last span included."""
    import jax.numpy as jnp
    cluster = DECODE_GRIDS[grid]
    shape = tuple(len(d.grid()) for d in cluster.dims)
    rng = np.random.default_rng(14)
    table = rng.integers(0, 64, size=shape).astype(np.float64)
    table[rng.random(shape) < 0.15] = np.inf
    table[-1, -1] = -1.0              # the unique minimum in the last span
    fn_np, fn_jx = (_table_param_fn(cluster, table, xp) for xp in (np, jnp))
    np_be, be = get_backend("numpy"), get_backend(backend)
    if method == "argmin_grid":
        tied = table.copy()
        tied[-1, -1] = 7.0            # ties decide the winner instead
        got = [be.argmin_grid(fn_jx, cluster, chunk_size=512),
               be.argmin_grid(_table_param_fn(cluster, tied, jnp), cluster,
                              chunk_size=512)]
        ref = [np_be.argmin_grid(fn_np, cluster),
               np_be.argmin_grid(_table_param_fn(cluster, tied, np),
                                 cluster)]
    else:
        pm = np.array([[0.0], [3.0], [-2.0]])
        got = be.argmin_grid_many(fn_jx, cluster, pm, chunk_size=512)
        ref = np_be.argmin_grid_many(fn_np, cluster, pm)
        # one request per grid value of each dim, each its own target
        ga, gb = (d.grid() for d in cluster.dims)
        n = max(len(ga), len(gb))
        targets = [(ga[k % len(ga)], gb[k % len(gb)]) for k in range(n)]
        got_t = be.argmin_grid_many(_target_fn(jnp), cluster,
                                    np.asarray(targets, float),
                                    chunk_size=512)
        assert got_t == [(t, 0.0) for t in targets]
    assert got[0][0] == cluster.max_config()
    assert got == ref


@needs_jax
@pytest.mark.parametrize("method", ["argmin_grid", "argmin_grid_many"])
def test_jax_scan_decodes_without_gather(method):
    """On a range grid the compiled jax scan programs hold no gather and
    no concatenate (no value table, no (rows, n_dims) stack), and their
    build counts no value-table dim; one explicit-value dim counts one."""
    import re

    import jax.numpy as jnp
    from repro.core.planning_backend import JaxPlanBackend
    from repro.obs import get_metrics
    smj = simulator_cost_models()["SMJ"]

    def fn(cfgs, params):
        return smj.cost_grid(params[0], params[1], cfgs, xp=jnp)

    def table_dims():
        return get_metrics().snapshot().get("backend.decode_table_dims", 0)

    be = JaxPlanBackend(devices=1)
    pm = np.array([[1.0, 5.0], [2.0, 3.0], [0.5, 9.0]])
    mixed = ClusterConditions(dims=(
        ResourceDim("nc", 1, 40), ResourceDim("cs", 1, 16,
                                              values=(1, 2, 4, 8, 16))))
    for cluster, n_table in ((scaled_cluster(40, 16), 0), (mixed, 1)):
        before = table_dims()
        if method == "argmin_grid":
            be.argmin_grid(fn, cluster, params=pm[0])
        else:
            be.argmin_grid_many(fn, cluster, pm)
        assert table_dims() - before == n_table
    (prog,) = [prog for key, (_, prog) in be._programs.items()
               if key[2] == scaled_cluster(40, 16).dims]
    p = be._params(pm[0] if method == "argmin_grid"
                   else np.pad(pm, ((0, 1), (0, 0))))     # Qpad = 4
    text = prog.lower(0, p).compile().as_text()
    assert not re.search(r"\b(gather|concatenate)\(", text)


# ------------------- scans that loop over their spans ---------------------- #
# The 1,240-row "range" grid scanned in spans of SPAN_ROWS rows: 7 spans,
# the last holding 40 rows.  argmin_grid's span is its chunk_size;
# argmin_grid_many's is _many_chunk's, set by the live-element cap.

SPAN_ROWS = 200


def _span_case(case, shape):
    """A cost table over ``shape`` whose winner sits where the looped
    scan must merge spans right."""
    rng = np.random.default_rng(17)
    table = rng.integers(8, 64, size=shape).astype(np.float64)
    flat = table.reshape(-1)                  # a view: writes land in table
    if case == "tie_across_spans":
        flat[[1210, 260, 700]] = 2.0          # spans 6, 1, 3: 260 wins
    elif case == "partial_last_span":
        flat[1230] = 1.0
    elif case == "all_inf":
        flat[:] = np.inf
    elif case == "nan":
        flat[5] = 1.0                         # span 0's finite minimum
        flat[910] = np.nan                    # a NaN in span 4 wins
    return table


def _oracle(cluster, costs):
    """The first minimum of ``costs`` in ``all_configs`` order, a NaN
    first (``np.argmin``); (None, inf) where every cost is inf."""
    k = int(np.argmin(costs.reshape(-1)))
    c = float(costs.reshape(-1)[k])
    if math.isinf(c):
        return None, math.inf
    return tuple(int(v) for v in enumerate_configs(cluster, k, k + 1)[0]), c


def _same(got, ref):
    (rg, cg), (rr, cr) = got, ref
    return rg == rr and (cg == cr or (math.isnan(cg) and math.isnan(cr)))


@needs_jax
@pytest.mark.parametrize("case", ["tie_across_spans", "partial_last_span",
                                  "all_inf", "nan"])
@pytest.mark.parametrize("method", ["argmin_grid", "argmin_grid_many"])
@pytest.mark.parametrize("backend", ["jax", "jax_x64"])
def test_looped_scan_matches_numpy_oracle_across_spans(backend, method,
                                                       case, monkeypatch):
    """A scan of many spans, looped on the device, returns the numpy
    oracle's config and cost bit for bit: ties between spans go to the
    lower row, a partial last span counts, an all-inf surface has no
    plan, a NaN wins.  It is one launch that sweeps every span."""
    import jax.numpy as jnp
    from repro.core import planning_backend as pb
    from repro.obs import get_metrics
    cluster = DECODE_GRIDS["range"]
    shape = tuple(len(d.grid()) for d in cluster.dims)
    table = _span_case(case, shape)
    fn = _table_param_fn(cluster, table, jnp)
    be = get_backend(backend)
    D = be.device_count()
    monkeypatch.setattr(pb, "MAX_LIVE_ELEMENTS", 2 * SPAN_ROWS // D)
    n_spans = -(-cluster.grid_size() // SPAN_ROWS)
    mx = get_metrics()

    def counts():
        snap = mx.snapshot()
        return (snap.get("backend.launches", 0),
                snap.get("backend.scan_spans", 0))
    before = counts()
    if method == "argmin_grid":
        got = [be.argmin_grid(fn, cluster, chunk_size=SPAN_ROWS // D)]
        ref = [_oracle(cluster, table)]
    else:
        pm = np.array([[0.0], [3.0]])
        got = be.argmin_grid_many(fn, cluster, pm)
        ref = [_oracle(cluster, table + p) for p in pm[:, 0]]
    after = counts()
    assert n_spans == 7
    assert (after[0] - before[0], after[1] - before[1]) == (1, n_spans)
    assert all(_same(g, r) for g, r in zip(got, ref)), (got, ref)
    if case == "tie_across_spans":
        assert got[0][0] == tuple(int(v) for v in
                                  enumerate_configs(cluster, 260, 261)[0])


@needs_jax
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), na=st.integers(3, 12),
       nb=st.integers(3, 9), ragged=st.booleans(),
       n_random=st.integers(0, 8))
def test_hypothesis_jax_numpy_ensemble_identical(seed, na, nb, ragged,
                                                 n_random):
    """Same seed -> same starts -> identical steepest-descent trajectories
    on both backends (first-min tie-breaking on neighbors)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    cluster = _random_cluster(rng, na, nb, ragged)
    table = _random_table(rng, na, nb)
    r_np, c_np = get_backend("numpy").hill_climb_ensemble(
        _table_fn(cluster, table, np), cluster, n_random=n_random, seed=seed)
    r_jx, c_jx = get_backend("jax").hill_climb_ensemble(
        _table_fn(cluster, table, jnp), cluster, n_random=n_random,
        seed=seed)
    assert r_jx == r_np
    assert (c_jx == c_np) or (math.isinf(c_jx) and math.isinf(c_np))


def test_ensemble_local_optimum_invariant_numpy():
    rng = np.random.default_rng(11)
    cluster = ClusterConditions(dims=(ResourceDim("a", 0, 20),
                                      ResourceDim("b", 0, 10)))
    table = rng.random((21, 11))
    res, cost = get_backend("numpy").hill_climb_ensemble(
        _table_fn(cluster, table, np), cluster, n_random=8, seed=3)
    assert cost == table[res]
    for d, delta in ((0, 1), (0, -1), (1, 1), (1, -1)):
        n = list(res)
        n[d] += delta
        if 0 <= n[0] <= 20 and 0 <= n[1] <= 10:
            assert table[tuple(n)] >= cost


def test_ensemble_more_starts_never_worse():
    """The vectorized multi-start ensemble must dominate the 2-corner
    climb in solution quality (it contains those corners)."""
    cluster = paper_cluster(30, 10)
    rng = np.random.default_rng(5)
    # multi-basin surface: three random attractors
    pts = [(int(rng.integers(1, 31)), int(rng.integers(1, 11)),
            float(rng.random() * 10)) for _ in range(3)]

    def fn(cfgs, params=None):
        a = np.asarray(cfgs, dtype=np.float64)
        return np.min(np.stack(
            [(a[:, 0] - x) ** 2 + (a[:, 1] - y) ** 2 + z
             for x, y, z in pts]), axis=0)

    be = get_backend("numpy")
    _, c2 = be.hill_climb_ensemble(fn, cluster)               # corners only
    _, c_ens = be.hill_climb_ensemble(fn, cluster, n_random=24, seed=0)
    _, c_opt = be.argmin_grid(fn, cluster)
    assert c_ens <= c2
    assert c_ens == pytest.approx(c_opt)    # 24 starts find the optimum here


def test_start_indices_dedupe_and_snap():
    cluster = ClusterConditions(dims=(
        ResourceDim("p2", 1, 16, values=(1, 2, 4, 8, 16)),
        ResourceDim("lin", 1, 4)))
    idx = start_indices(cluster, [(5, 3), (4, 3)], 0, 0)   # both snap to 4
    assert len(idx) == 1
    idx = start_indices(cluster, None, 6, seed=0)
    assert len(idx) <= 8                       # corners + 6, deduped
    assert tuple(idx[0]) == (0, 0) and tuple(idx[1]) == (4, 3)


def test_params_are_threaded():
    """params reach the cost fn on both entry points (budget masking)."""
    cluster = paper_cluster(10, 4)

    def fn(cfgs, params):
        a = np.asarray(cfgs, dtype=np.float64)
        cost = 1000.0 / a[:, 0] + a[:, 1]
        return np.where(a[:, 0] > params[0], np.inf, cost)

    be = get_backend("numpy")
    r1, _ = be.argmin_grid(fn, cluster, params=np.asarray([10.0]))
    r2, _ = be.argmin_grid(fn, cluster, params=np.asarray([4.0]))
    assert r1[0] == 10 and r2[0] == 4
    r3, _ = be.hill_climb_ensemble(fn, cluster,
                                   params=np.asarray([4.0]))
    assert r3[0] <= 4


# ----------------- roofline: terms_grid == terms_for ----------------------- #

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", SHAPES)
def test_terms_grid_bit_identical_to_scalar(arch, shape_name):
    """The numpy grid roofline is bit-identical (not merely close) to the
    scalar terms_for over the full TPU grid, for every plan choice."""
    cfg, shape = get_config(arch), get_shape(shape_name)
    dims = TpuCluster().dims(shape)
    cfgs = enumerate_configs(dims)
    for choice in PLAN_CHOICES[shape.kind]:
        if cfg.family == "ssm" and choice.get("schedule") == "causal_skip":
            continue
        g = terms_grid(cfg, shape, cfgs, **choice)
        for i, row in enumerate(cfgs):
            t = terms_for(cfg, shape, Resources(*(int(v) for v in row)),
                          **choice)
            assert g.compute_s[i] == t.compute_s
            assert g.memory_s[i] == t.memory_s
            assert g.collective_s[i] == t.collective_s
            assert g.hbm_per_chip[i] == t.hbm_per_chip
            assert bool(g.feasible[i]) == t.feasible
            assert g.step_s[i] == t.step_s


@needs_jax
def test_terms_grid_jax_within_fp_tolerance():
    import jax.numpy as jnp
    for arch, shape_name in (("deepseek-67b", "train_4k"),
                             ("qwen3-moe-30b-a3b", "decode_32k"),
                             ("zamba2-2.7b", "prefill_32k")):
        cfg, shape = get_config(arch), get_shape(shape_name)
        dims = TpuCluster().dims(shape)
        cfgs = enumerate_configs(dims)
        choice = PLAN_CHOICES[shape.kind][0]
        g64 = terms_grid(cfg, shape, cfgs, **choice)
        g32 = terms_grid(cfg, shape, jnp.asarray(cfgs), xp=jnp, **choice)
        np.testing.assert_allclose(np.asarray(g32.step_s), g64.step_s,
                                   rtol=5e-5)
        np.testing.assert_allclose(np.asarray(g32.hbm_per_chip),
                                   g64.hbm_per_chip, rtol=5e-5)


# ------------- sharding planner: vectorized == scalar path ----------------- #

def _scalar_joint(planner: ShardingPlanner, cfg, shape, chip_budget=None):
    """The pre-backend scalar search path (hill_climb_multi over scalar
    terms_for, brute-force fallback), kept as the reference oracle."""
    dims = planner.cluster.dims(shape)
    best = None
    for choice in PLAN_CHOICES[shape.kind]:
        if cfg.family == "ssm" and choice.get("schedule") == "causal_skip":
            continue
        fn = planner._cost_fn(cfg, shape, choice, chip_budget)
        res, cost = hill_climb_multi(fn, dims)
        if not math.isfinite(cost):
            res, cost = brute_force(fn, dims)
        if res is None or not math.isfinite(cost):
            continue
        if best is None or cost < best[0]:
            best = (cost, tuple(res), choice)
    return best


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape_name", SHAPES)
def test_vectorized_joint_matches_scalar_path(arch, shape_name):
    cfg, shape = get_config(arch), get_shape(shape_name)
    planner = ShardingPlanner()
    d = planner.joint(cfg, shape)
    ref = _scalar_joint(planner, cfg, shape)
    assert ref is not None
    cost, res, choice = ref
    assert d.resources.as_tuple() == res
    assert d.plan_choice == choice
    assert d.objective_value == cost


@needs_jax
def test_jax_joint_matches_numpy_joint():
    for arch, shape_name in (("deepseek-67b", "train_4k"),
                             ("smollm-360m", "train_4k"),
                             ("qwen3-moe-30b-a3b", "decode_32k")):
        cfg, shape = get_config(arch), get_shape(shape_name)
        dn = ShardingPlanner(backend="numpy").joint(cfg, shape)
        dj = ShardingPlanner(backend="jax").joint(cfg, shape)
        assert dj.resources == dn.resources
        assert dj.plan_choice == dn.plan_choice
        # both objective values commit through the scalar float64 path
        assert dj.objective_value == dn.objective_value


def test_ensemble_planner_never_worse_than_hillclimb():
    cfg, shape = get_config("deepseek-67b"), get_shape("train_4k")
    d_hc = ShardingPlanner(resource_planning="hillclimb").joint(cfg, shape)
    d_en = ShardingPlanner(resource_planning="ensemble").joint(cfg, shape)
    d_bf = ShardingPlanner(resource_planning="brute").joint(cfg, shape)
    assert d_en.objective_value <= d_hc.objective_value + 1e-12
    assert d_bf.objective_value <= d_en.objective_value + 1e-12


# --------------- DB domain: jax == numpy through OperatorCosting ----------- #

@needs_jax
@pytest.mark.parametrize("objective", ["time", "money"])
def test_operator_costing_jax_matches_numpy(objective):
    cluster = paper_cluster(100, 10)
    kw = dict(models=simulator_cost_models(), cluster=cluster,
              objective=objective)
    for ss, ls in ((0.5, 74.0), (2.0, 10.0), (6.0, 200.0)):
        c_np = OperatorCosting(resource_planning="batched", **kw)
        c_jx = OperatorCosting(resource_planning="batched", backend="jax",
                               **kw)
        r_np, cost_np = c_np.plan_resources("SMJ", ss, ls)
        r_jx, cost_jx = c_jx.plan_resources("SMJ", ss, ls)
        assert r_jx == r_np
        # winner re-costed through the scalar float64 path on both ends
        assert cost_jx == pytest.approx(cost_np, rel=1e-12)


@needs_jax
def test_operator_costing_jax_reuses_compiled_program():
    """ss/ls travel as traced params: one (impl, objective) fn object ->
    one backend program across operators with different data sizes."""
    c = OperatorCosting(models=simulator_cost_models(),
                        cluster=paper_cluster(50, 10),
                        resource_planning="batched", backend="jax")
    c.plan_resources("SMJ", 2.0, 74.0)
    fn1 = c._grid_fn_cache.get(("SMJ", "time", "jax"))
    c.begin_query()
    c.plan_resources("SMJ", 5.0, 200.0)
    assert c._grid_fn_cache.get(("SMJ", "time", "jax")) is fn1


# ------------------- CI backend-matrix lane (conftest fixture) -------------- #

def test_env_backend_lane_matches_numpy(plan_backend):
    """This suite's random-grid parity (exhaustive scan + ensemble
    climb), retargeted at whatever backend the CI matrix lane selected
    via REPRO_PLAN_BACKEND (the numpy lane degenerates to oracle ==
    oracle; integer tables keep f32 lanes exact)."""
    rng = np.random.default_rng(7)
    xp = plan_backend.xp
    for ragged in (False, True):
        cluster = _random_cluster(rng, 9, 7, ragged)
        table = _random_table(rng, 9, 7)
        r_np, c_np = get_backend("numpy").argmin_grid(
            _table_fn(cluster, table, np), cluster)
        r_e, c_e = plan_backend.argmin_grid(
            _table_fn(cluster, table, xp), cluster)
        assert r_e == r_np
        assert (c_e == c_np) or (math.isinf(c_e) and math.isinf(c_np))
        e_np = get_backend("numpy").hill_climb_ensemble(
            _table_fn(cluster, table, np), cluster, n_random=6, seed=3)
        e_env = plan_backend.hill_climb_ensemble(
            _table_fn(cluster, table, xp), cluster, n_random=6, seed=3)
        assert e_env[0] == e_np[0] and e_env[1] == e_np[1]


def test_operator_costing_ensemble_never_worse_than_2start():
    cluster = paper_cluster(100, 10)
    kw = dict(models=simulator_cost_models(), cluster=cluster)
    for ss, ls in ((0.5, 74.0), (2.0, 74.0), (6.0, 200.0)):
        c2 = OperatorCosting(resource_planning="hillclimb_batched", **kw)
        ce = OperatorCosting(resource_planning="ensemble", **kw)
        _, cost2 = c2.plan_resources("SMJ", ss, ls)
        _, cost_e = ce.plan_resources("SMJ", ss, ls)
        assert cost_e <= cost2 + 1e-12
