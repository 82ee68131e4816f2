"""Backend-agnostic array-planning layer: one search engine for both domains.

The paper's architecture (Fig. 8) inserts resource planning *inside* the
query optimizer's cost loop, which only works if planning a resource
configuration is about as cheap as evaluating a cost model once (§VII
reports up to 16x overhead reduction, scaling to 100K-container clusters).
This module is that engine, factored out of the per-domain planners: the
DB-domain ``OperatorCosting`` (plans.py) and the TPU-domain
``ShardingPlanner`` (sharding_planner.py) both drive the same three
primitives over a discrete resource grid (``ClusterConditions``):

    enumerate_configs   row [lo, hi) slices of the full grid, in
                        ``all_configs`` order (tie-breaking contract)
    argmin_grid         exhaustive scan in bounded-memory chunks
                        (the vectorized form of §VI-B1 brute force)
    hill_climb_ensemble multi-start steepest-descent climbing: every ±1
                        neighbor of every active start costed per
                        iteration as ONE batch (the batched form of
                        Algorithm 1, §VI-B2, generalised from 2 corner
                        starts to an ensemble of random starts)

Two implementations of the ``PlanBackend`` protocol:

* ``NumpyPlanBackend`` — float64 chunked numpy.  Arithmetic is
  bit-identical to the scalar Python loops (cost models share one
  elementwise expression between scalar and grid paths), so batched and
  scalar search return the *same* argmin, ties included.
* ``JaxPlanBackend`` — jax.jit-compiled.  The grid-chunk scan and the
  whole ensemble climb (a ``lax.while_loop``) each run as one fused XLA
  program, so the roofline cost models fuse with the search itself.
  Programs are cached per (cost-fn object, grid): callers that reuse
  their batch-cost function across plan requests pay tracing/compilation
  once and amortise it over every subsequent operator (the paper's
  recurring-job story, §V).  Scalar parameters that vary per request
  (data sizes, budgets) are *traced arguments* — pass them via
  ``params`` — so a new (ss, ls) does not recompile.

Batch-cost-fn contract
----------------------
``fn(configs)`` or ``fn(configs, params)`` -> costs, where ``configs`` is
an ``(N, n_dims)`` integer array of resource configurations (rows in grid
units, e.g. ``(nc, cs)`` or ``(pods, dp, tp, microbatch)``) and ``params``
is a small float vector of per-request scalars.  Infeasible
configurations must cost ``inf``.  For the jax backend the fn must be
traceable (build it from ``backend.xp`` ops; every cost model in this
repo takes an ``xp`` argument for exactly this).  The jax-family grid
scans decode their configurations by arithmetic into a ``ConfigColumns``
view, one column per dimension, rather than an array: a fn reads it
through ``as_configs`` (``configs[:, d]``, ``shape``), and
``jnp.asarray`` still stacks it for a fn that indexes rows.

Many-request primitives
-----------------------
``argmin_grid_many`` and ``hill_climb_ensemble_many`` evaluate a whole
*batch* of planning requests that share one cost fn and one grid but
differ in ``params``: the request scalars are stacked into a ``(Q, P)``
array and the search runs for all Q requests at once.  On numpy the
params enter the cost expression as ``(Q, 1)`` columns broadcasting
against the ``(M,)`` config columns — the same float64 elementwise
arithmetic as the per-request path, so the stacked argmins are
bit-identical with Q independent scans.  On jax the per-request cost /
climb is ``jax.vmap``-ed over the params axis and jitted as ONE program
(config enumeration hoisted out of the vmap, request count padded to
even so the compiled shape set stays small).
This is the engine under ``repro.core.plan_broker``: one fused program
call plans every operator of every concurrent query.

Precision
---------
``JaxPlanBackend(precision="x64")`` (``get_backend("jax_x64")``) scopes
every trace and call in ``jax.enable_x64``, so the compiled
programs compute in float64 and argmin selection is exact — float32
rounding can no longer flip a winner, and the planners' float64
re-commit fallback shrinks to a parity assertion.  Backends advertise
this via ``backend.exact`` (True for numpy and jax_x64).

Multi-device sharding
---------------------
When more than one local device is visible (real TPU/GPU hosts, or CPU
hosts under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``), the
jax-family backends partition the **config axis** of every grid scan
over a 1-D ``"plan"`` mesh (``repro.launch.mesh.plan_mesh``) via
``shard_map``: each dispatch covers a contiguous span of ``D * chunk``
flat row ids, every device reduces its own contiguous ``chunk``-row
shard to a ``(best_cost, best_flat)`` pair on-device, and the cross-shard
fold happens *inside* the jitted program.  Because the flat row ids are
globally ordered and each shard holds an ascending contiguous range,
``jnp.argmin`` over the per-shard bests (first minimum = lowest device =
lowest rows) reproduces the strict-< first-minimum tie-break exactly, so
sharded results are bit-identical with the single-device and numpy
paths.  The stacked ensemble climb shards the *request* axis instead
(vmap lanes are independent, so trajectories are unchanged).  The host
still performs one ``np.asarray`` sync per call.  ``REPRO_PLAN_DEVICES``
caps the device count (``1`` disables sharding); the ``devices`` ctor
arg caps it per backend instance.

Async dispatch (broker double-buffering)
----------------------------------------
``argmin_grid_many_async`` / ``hill_climb_ensemble_many_async`` enqueue
one program on device and return a zero-arg ``finalize`` closure that
performs the single host sync and decodes results.  A scan whose grid
takes more than one span (``_many_chunk`` and the device count set a
span's rows) loops over its spans inside that program, a ``fori_loop``
carrying each request's first minimum (``_over_spans``), so the host
launches one program per scan however large the grid.  The broker's
double-buffered flush waves are built on exactly this split: wave N's
programs execute on device while the Selinger / FastRandomized drivers
enumerate and submit wave N+1 (see ``repro.core.plan_broker``).  The
numpy backend computes eagerly and defers only the return, keeping the
wave machinery backend-uniform.

Program names and counters
--------------------------
Every program the jax backend launches carries its stage (and, for the
stacked scan, its padded width) in its name, so that profiles, HLO
dumps and compile logs attribute device time without guessing from
shapes: ``plan_scan_many_w{Qpad}``, ``plan_scan``, ``plan_climb_many``
and ``plan_climb``; params reach the device as a plain transfer, no
program.  Five counters are always on (``repro.obs.ALWAYS_ON``), one
increment per scan call or program build: ``backend.launches`` (scan
programs enqueued, one per scan call), ``backend.scan_spans`` (the
spans those programs sweep, so ``scan_spans / launches`` is the mean
trip count of the span loop), ``backend.programs_built`` (memo
misses), ``backend.decode_table_dims`` (the dimensions a built scan
program decodes from a value table rather than by arithmetic) and
``backend.compiles`` (XLA compiles and compile-cache loads, from a
``jax.monitoring`` listener registered when the first jax backend is
built).  Traced, a scan's launch is the ``backend.launch`` span
(``launches=1``, ``spans``) and each compile a ``backend.compile``
span.
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.analysis.registry import hot_path
from repro.core.cluster import (ClusterConditions, ConfigColumns,
                                PlanningStats)
from repro.core.plan_cache import snap_to_grid
from repro.obs import get_metrics, get_tracer

_obs = get_tracer()
_metrics = get_metrics()

BatchCostFn = Callable[..., "np.ndarray"]
Result = Tuple[Optional[Tuple[int, ...]], float]

DEFAULT_CHUNK = 1 << 20

# Stacked-scan chunk sizing (see _many_chunk): shards never shrink below
# MIN_SHARD_ROWS rows, and the live per-dispatch cost block — (Q, chunk)
# elements per device — never exceeds MAX_LIVE_ELEMENTS.
MIN_SHARD_ROWS = 512
MAX_LIVE_ELEMENTS = 1 << 22


# --------------------------- names and compiles ---------------------------- #

def _named(fn: Callable, name: str) -> Callable:
    """``fn`` renamed to ``name``: ``jax.jit`` names its program after
    the function (``jit_<name>`` in HLO, profiles and compile logs)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _pick(a, b):
    """Of two ``(cost, flat)`` pairs, elementwise, the one
    ``jnp.argmin``'s comparator keeps: the lesser cost (a NaN wins, ``a``
    first), on equal costs the lower flat row id."""
    from jax import lax
    (ac, af), (bc, bf) = a, b
    take = (ac < bc) | (ac != ac)
    take_f = take | ((ac == bc) & (af < bf))
    return lax.select(take, ac, bc), lax.select(take_f, af, bf)


def _first_min(costs, flats, axis: int):
    """``(best_cost, best_flat)`` along ``axis``: the least cost and the
    lowest flat row id attaining it, in ONE variadic reduction under
    ``_pick``.  ``flats`` (broadcast against ``costs``) ascend along
    ``axis``, so this is the strict-< first minimum, and no gather reads
    the winner back."""
    import jax.numpy as jnp
    from jax import lax
    flats = jnp.broadcast_to(flats, costs.shape)
    init = (np.array(np.inf, costs.dtype),
            np.array(np.iinfo(flats.dtype).max, flats.dtype))
    return lax.reduce((costs, flats), init, _pick, (axis,))


def _over_spans(body: Callable, n_spans: int, span: int) -> Callable:
    """``body(lo, p) -> (best_cost, best_flat)``, the scan of the ``span``
    rows from ``lo``, made into the scan of ``n_spans`` consecutive spans
    from ``lo``: ``body`` itself for one span, else a ``fori_loop`` on
    the device that carries ``(best_cost, best_flat)`` from ``(inf, max
    id)`` and merges each span's result into it, carry first, under
    ``_first_min``'s comparator (``_pick``).  The carry holds the lower
    rows, so the first span holding the minimum wins, as in a sequential
    fold."""
    if n_spans == 1:
        return body
    import jax
    import jax.numpy as jnp

    def sweep(lo, p):
        cost, flat = jax.eval_shape(body, lo, p)
        init = (jnp.full(cost.shape, np.inf, cost.dtype),
                jnp.full(flat.shape, np.iinfo(flat.dtype).max, flat.dtype))

        def step(i, best):
            return _pick(best, body(lo + i.astype(flat.dtype) * span, p))
        return jax.lax.fori_loop(0, n_spans, step, init)
    return sweep


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener = []             # the registered listener, once


def _on_compile(event: str, start_s: float, end_s: float, **kw) -> None:
    """``jax.monitoring`` time-span listener: one XLA compile, or one
    load from the persistent compilation cache, has ended.  Counted
    always; traced, it becomes a ``backend.compile`` span, its
    ``time.time()`` stamps moved onto the tracer's clock."""
    if event != _COMPILE_EVENT:
        return
    _metrics.counter("backend.compiles").inc()
    if _obs.enabled:
        shift = time.perf_counter_ns() - time.time_ns()
        _obs.complete("backend.compile", int(start_s * 1e9) + shift,
                      cat="compile", end_ns=int(end_s * 1e9) + shift,
                      fun_name=kw.get("fun_name", "?"))


def _listen_for_compiles(jax) -> None:
    """Register ``_on_compile`` with jax once per process."""
    if not _compile_listener:
        jax.monitoring.register_event_time_span_listener(_on_compile)
        _compile_listener.append(_on_compile)


# ----------------------------- grid helpers -------------------------------- #

def grid_arrays(cluster: ClusterConditions) -> List[np.ndarray]:
    """Per-dimension value grids as int64 arrays."""
    return [np.asarray(d.values, dtype=np.int64) if d.values
            else np.arange(d.lo, d.hi + 1, d.step, dtype=np.int64)
            for d in cluster.dims]


def enumerate_configs(cluster: ClusterConditions, lo: int = 0,
                      hi: Optional[int] = None) -> np.ndarray:
    """Rows [lo, hi) of the full resource grid as an (M, n_dims) int array,
    in the exact order ``cluster.all_configs()`` yields tuples (row-major:
    first dimension slowest)."""
    grids = grid_arrays(cluster)
    shape = tuple(len(g) for g in grids)
    total = int(np.prod(shape)) if shape else 0
    hi = total if hi is None else min(hi, total)
    flat = np.arange(lo, hi, dtype=np.int64)
    idx = np.unravel_index(flat, shape)
    return np.stack([g[i] for g, i in zip(grids, idx)], axis=1)


# ------------------------------- grid decode -------------------------------- #
# One decode serves every scan program: flat row ids become configuration
# values by arithmetic, so no program gathers from a value table or stacks
# an (N, n_dims) array (a TPU has no vector gather, and a minor dimension
# of n_dims is a relayout its compiler handles badly).

def _dim_meta(cluster: ClusterConditions) -> Tuple[Tuple, ...]:
    """Static per-dimension decode recipe: ("affine", lo, step) for range
    dims (value = lo + step * idx, pure arithmetic) or ("values", vals)
    for explicit grids (compare-select over the small value table).
    Called once per program build, which ``backend.decode_table_dims``
    counts by its value-table dims."""
    metas = tuple(("values", tuple(int(v) for v in d.values)) if d.values
                  else ("affine", int(d.lo), int(d.step))
                  for d in cluster.dims)
    _metrics.counter("backend.decode_table_dims").inc(
        sum(m[0] == "values" for m in metas))
    return metas


def _dim_sizes(cluster: ClusterConditions) -> Tuple[int, ...]:
    return tuple(d.size for d in cluster.dims)


def _value_of_index(idx, meta):
    """One dimension's grid indices -> config values (same shape and
    integer dtype)."""
    import jax.numpy as jnp
    if meta[0] == "affine":
        _, lo, step = meta
        return lo + step * idx
    vals = meta[1]
    col = jnp.full_like(idx, vals[0])
    for k in range(1, len(vals)):
        col = jnp.where(idx == k, vals[k], col)
    return col


def _decode_columns(flat, metas, sizes):
    """Flat row ids -> one array of config values per dimension, in
    ``enumerate_configs`` order (row-major, first dim slowest), decoded
    by a divmod chain by the static dim sizes from the fastest dim up."""
    cols = [None] * len(sizes)
    rem = flat
    for d in range(len(sizes) - 1, 0, -1):
        cols[d] = _value_of_index(rem % sizes[d], metas[d])
        rem = rem // sizes[d]
    cols[0] = _value_of_index(rem, metas[0])
    return cols


def start_indices(cluster: ClusterConditions,
                  starts: Optional[Sequence[Sequence[int]]],
                  n_random: int, seed: int) -> np.ndarray:
    """Ensemble start points as grid *indices* (S, n_dims).

    Defaults to the min+max corners (the two starts bracketing 1/x-shaped
    cost surfaces) plus ``n_random`` uniform grid points.  Explicit
    ``starts`` (config values, possibly off-grid) are snapped through
    ``snap_to_grid`` so every backend explores the same basins.  Both
    backends draw from the same seeded numpy generator, so numpy and jax
    ensembles are start-for-start identical.
    """
    grids = grid_arrays(cluster)
    if starts is None:
        base = [cluster.min_config(), cluster.max_config()]
    else:
        base = [tuple(s) for s in starts]
    idx = [_snap_to_indices(s, cluster, grids) for s in base]
    if n_random > 0:
        rng = np.random.default_rng(seed)
        rand = np.stack([rng.integers(0, len(g), size=n_random)
                         for g in grids], axis=1)
        idx.extend(rand.tolist())
    # dedupe while preserving order (corners first)
    seen, uniq = set(), []
    for row in idx:
        t = tuple(int(v) for v in row)
        if t not in seen:
            seen.add(t)
            uniq.append(t)
    return np.asarray(uniq, dtype=np.int64)


def _snap_to_indices(cfg: Sequence[int], cluster: ClusterConditions,
                     grids: List[np.ndarray]) -> List[int]:
    # go through snap_to_grid so every backend snaps an off-grid start to
    # the *same* configuration; the result is exactly on the grid, so
    # argmin finds the exact index
    snapped = snap_to_grid(tuple(cfg), cluster)
    return [int(np.argmin(np.abs(g - v))) for g, v in zip(grids, snapped)]


def _decode_flat(cluster: ClusterConditions, flat: int) -> Tuple[int, ...]:
    """A flat row id -> its configuration, in ``enumerate_configs`` order:
    the host twin of ``_decode_columns``, by arithmetic (no grid is
    built, so a scan's winners decode in O(n_dims))."""
    dims = cluster.dims
    cfg = [0] * len(dims)
    rem = int(flat)
    for d in range(len(dims) - 1, 0, -1):
        rem, i = divmod(rem, dims[d].size)
        cfg[d] = int(dims[d].value(i))
    cfg[0] = int(dims[0].value(rem))
    return tuple(cfg)


def _pad_even(n: int) -> int:
    """Next even number >= n: the padded request count for stacked jax
    programs — halves the distinct compiled batch shapes at <= one padded
    lane of waste (pow2 padding wastes up to ~2x work on odd sizes)."""
    return n + (n & 1)


def _pad_multiple(n: int, m: int) -> int:
    """Round ``n`` up to a multiple of ``m`` (the device-even padding for
    sharded scans and request-axis-sharded climbs)."""
    return -(-n // m) * m


def _many_chunk(total: int, q: int, n_dev: int, chunk_size: int) -> int:
    """Per-device rows per dispatch for a stacked Q-request grid scan.

    The naive ``chunk_size // q`` floors to one-row shards for large Q,
    which degenerates a sharded scan into pure dispatch overhead — so the
    chunk is floored at ``MIN_SHARD_ROWS``, then capped so the live
    per-dispatch cost block (``q * chunk`` elements per device) never
    exceeds ``MAX_LIVE_ELEMENTS``, and finally clipped to the per-device
    share ``ceil(total / n_dev)`` so one dispatch never pads past a full
    grid sweep.  The argmin is invariant to chunking (strict-< fold), so
    this only changes dispatch geometry, never results.
    """
    q = max(1, q)
    chunk = max(chunk_size // q, MIN_SHARD_ROWS)
    chunk = min(chunk, max(1, MAX_LIVE_ELEMENTS // q))
    return int(min(chunk, -(-total // max(1, n_dev))))


def _neighbor_offsets(n_dims: int) -> np.ndarray:
    """(2*n_dims, n_dims) index offsets: one -1 and one +1 step per dim,
    exactly the candidate set initialised on line 2 of Algorithm 1."""
    offs = np.zeros((2 * n_dims, n_dims), dtype=np.int64)
    for d in range(n_dims):
        offs[2 * d, d] = -1
        offs[2 * d + 1, d] = 1
    return offs


# ------------------------------ numpy backend ------------------------------ #

class NumpyPlanBackend:
    """Chunked float64 numpy search; bit-identical with the scalar loops."""

    name = "numpy"
    xp = np
    exact = True                  # float64 end-to-end: argmins are exact
    precision = "float64"

    def _call(self, fn: BatchCostFn, cfgs: np.ndarray, params) -> np.ndarray:
        out = fn(cfgs) if params is None else fn(cfgs, params)
        return np.asarray(out, dtype=np.float64)

    def argmin_grid(self, batch_cost_fn: BatchCostFn,
                    cluster: ClusterConditions,
                    stats: Optional[PlanningStats] = None, *,
                    params=None, chunk_size: int = DEFAULT_CHUNK) -> Result:
        """Exhaustive vectorized scan of the grid in bounded-memory chunks.
        Returns the first (in ``all_configs`` order) strict minimum,
        matching scalar brute-force tie-breaking; (None, inf) if every
        configuration costs inf."""
        stats = stats if stats is not None else PlanningStats()
        total = cluster.grid_size()
        best_cfg: Optional[Tuple[int, ...]] = None
        best_cost = math.inf
        for lo in range(0, total, chunk_size):
            cfgs = enumerate_configs(cluster, lo, lo + chunk_size)
            costs = self._call(batch_cost_fn, cfgs, params)
            stats.configs_explored += len(cfgs)
            i = int(np.argmin(costs))
            if costs[i] < best_cost:
                best_cfg = tuple(int(v) for v in cfgs[i])
                best_cost = float(costs[i])
        return best_cfg, best_cost

    def hill_climb_ensemble(self, batch_cost_fn: BatchCostFn,
                            cluster: ClusterConditions,
                            starts: Optional[Sequence[Sequence[int]]] = None,
                            stats: Optional[PlanningStats] = None, *,
                            params=None, n_random: int = 0, seed: int = 0,
                            max_iters: int = 100_000) -> Result:
        """Batched multi-start steepest-descent climbing.

        Every iteration costs all ±1 neighbors of all still-active starts
        as a single batch; a start deactivates when no neighbor improves
        it (the same "no better neighbors exist" invariant that
        terminates Algorithm 1).  Returns the best local optimum over the
        ensemble."""
        stats = stats if stats is not None else PlanningStats()
        grids = grid_arrays(cluster)
        sizes = np.array([len(g) for g in grids], dtype=np.int64)
        n_dims = len(grids)

        def values_of(idx: np.ndarray) -> np.ndarray:
            return np.stack([grids[d][idx[:, d]] for d in range(n_dims)],
                            axis=1)

        cur = start_indices(cluster, starts, n_random, seed)
        cur_cost = self._call(batch_cost_fn, values_of(cur), params)
        stats.configs_explored += len(cur)
        active = np.ones(len(cur), dtype=bool)
        offs = _neighbor_offsets(n_dims)

        for _ in range(max_iters):
            act = np.flatnonzero(active)
            if act.size == 0:
                break
            # every ±1 neighbor of every active point: (A, 2*n_dims, n_dims)
            nbr = cur[act][:, None, :] + offs[None, :, :]
            flat = nbr.reshape(-1, n_dims)
            valid = ((flat >= 0) & (flat < sizes)).all(axis=1)
            costs = np.full(len(flat), np.inf)
            if valid.any():
                costs[valid] = self._call(batch_cost_fn,
                                          values_of(flat[valid]), params)
                stats.configs_explored += int(valid.sum())
            costs = costs.reshape(act.size, 2 * n_dims)
            best_j = np.argmin(costs, axis=1)
            best_c = costs[np.arange(act.size), best_j]
            improved = best_c < cur_cost[act]
            moved = act[improved]
            cur[moved] = nbr[improved, best_j[improved]]
            cur_cost[moved] = best_c[improved]
            active[:] = False
            active[moved] = True

        i = int(np.argmin(cur_cost))
        res = tuple(int(v) for v in values_of(cur[i:i + 1])[0])
        return res, float(cur_cost[i])

    # -- stacked many-request search ----------------------------------------- #
    def argmin_grid_many(self, batch_cost_fn: BatchCostFn,
                         cluster: ClusterConditions,
                         params_many, *,
                         stats: Optional[PlanningStats] = None,
                         chunk_size: int = DEFAULT_CHUNK) -> List[Result]:
        """Exhaustive scan for Q requests sharing one cost fn and grid.

        ``params_many`` is ``(Q, P)``; the fn sees ``params`` whose k-th
        entry is the ``(Q, 1)`` column of per-request scalars, which
        broadcasts against the ``(M,)`` config columns into a ``(Q, M)``
        cost matrix — identical float64 elementwise arithmetic to Q
        separate scans, so plans and costs are bit-identical with the
        per-request ``argmin_grid`` (first-strict-minimum ties included;
        the argmin is invariant to the smaller per-request chunk)."""
        stats = stats if stats is not None else PlanningStats()
        pm = np.asarray(params_many, dtype=np.float64)
        Q = pm.shape[0]
        if Q == 0:
            return []
        total = cluster.grid_size()
        p = pm.T[:, :, None]                      # params[k] -> (Q, 1)
        chunk = _many_chunk(total, Q, 1, chunk_size)  # bounded: Q*chunk live
        best_cost = np.full(Q, np.inf)
        best_flat = np.full(Q, -1, dtype=np.int64)
        for lo in range(0, total, chunk):
            cfgs = enumerate_configs(cluster, lo, lo + chunk)
            out = np.asarray(batch_cost_fn(cfgs, p), dtype=np.float64)
            costs = np.broadcast_to(out, (Q, len(cfgs)))
            stats.configs_explored += Q * len(cfgs)
            j = np.argmin(costs, axis=1)
            c = costs[np.arange(Q), j]
            upd = c < best_cost
            best_cost[upd] = c[upd]
            best_flat[upd] = lo + j[upd]
        return [(None, math.inf) if best_flat[q] < 0 else
                (_decode_flat(cluster, best_flat[q]),
                 float(best_cost[q])) for q in range(Q)]

    def hill_climb_ensemble_many(self, batch_cost_fn: BatchCostFn,
                                 cluster: ClusterConditions,
                                 params_many, *,
                                 starts=None,
                                 stats: Optional[PlanningStats] = None,
                                 n_random: int = 0, seed: int = 0,
                                 max_iters: int = 100_000) -> List[Result]:
        """Ensemble climbs for Q requests sharing one fn/grid/start set.
        Runs the (already batched-over-starts) per-request climb once per
        request — trivially bit-identical with the per-request path; the
        jax backend fuses the whole Q-batch instead."""
        pm = np.asarray(params_many, dtype=np.float64)
        return [self.hill_climb_ensemble(
            batch_cost_fn, cluster, starts, stats, params=pm[q],
            n_random=n_random, seed=seed, max_iters=max_iters)
            for q in range(pm.shape[0])]

    # -- async variants (double-buffered broker waves) ----------------------- #
    # numpy is synchronous: compute eagerly and defer only the return, so
    # the broker's wave machinery stays backend-uniform (and the wave
    # commit order — hence cache contents — is identical across backends)
    def argmin_grid_many_async(self, *args, **kwargs):
        res = self.argmin_grid_many(*args, **kwargs)
        return lambda: res

    def hill_climb_ensemble_many_async(self, *args, **kwargs):
        res = self.hill_climb_ensemble_many(*args, **kwargs)
        return lambda: res


# ------------------------------- jax backend ------------------------------- #

class JaxPlanBackend:
    """jax.jit search programs; the cost model fuses with the search.

    Compiled programs are memoized per (batch-cost-fn object, grid
    signature): reuse the same fn object across requests (vary the data
    via ``params``) and only the first request traces/compiles.  Numeric
    note: with the default ``precision="float32"`` argmins agree with the
    float64 backends up to fp tolerance, which is why the planners
    re-evaluate the winning configuration through the scalar float64 path
    before committing to it; ``precision="x64"`` scopes every trace and
    call in ``jax.enable_x64`` so selection is exact
    (``self.exact``) and that fallback shrinks to a parity assertion.
    """

    MAX_PROGRAMS = 128                     # FIFO bound on compiled programs

    def __init__(self, precision: str = "float32",
                 devices: Optional[int] = None):
        import jax                         # noqa: F401 — fail fast if absent
        import jax.numpy as jnp
        if precision not in ("float32", "x64"):
            raise ValueError(f"unknown jax precision {precision!r} "
                             "(expected 'float32' or 'x64')")
        from jax import shard_map
        _listen_for_compiles(jax)
        self._jax = jax
        self.xp = jnp
        self._shard_map = shard_map
        self.precision = precision
        self.exact = precision == "x64"
        self.name = "jax" if precision == "float32" else "jax_x64"
        self._programs = {}                # key -> (fn_ref, compiled)
        self._devices = devices            # ctor cap on the plan mesh size
        self._ndev: Optional[int] = None
        self._mesh = None

    def _scope(self):
        """x64-scoped tracing/execution for precision="x64"; no-op else."""
        if self.exact:
            return self._jax.enable_x64()
        return contextlib.nullcontext()

    # -- plan mesh ----------------------------------------------------------- #
    def device_count(self) -> int:
        """Devices the config axis is sharded over: the local device count
        capped by REPRO_PLAN_DEVICES and the ``devices`` ctor arg.  1 means
        the sharded paths are bypassed (legacy single-device programs)."""
        if self._ndev is None:
            from repro.launch.mesh import plan_device_count
            n = plan_device_count()
            if self._devices is not None:
                n = min(n, max(1, int(self._devices)))
            self._ndev = max(1, n)
        return self._ndev

    def _plan_mesh(self):
        """The 1-D "plan" mesh sharded scan programs are built over."""
        if self._mesh is None:
            from repro.launch.mesh import plan_mesh
            self._mesh = plan_mesh(self.device_count())
        return self._mesh

    # -- program cache ------------------------------------------------------ #
    def _program(self, kind: str, fn: BatchCostFn,
                 cluster: ClusterConditions, extra, build):
        key = (kind, id(fn), cluster.dims, extra)
        hit = self._programs.get(key)
        if hit is not None and hit[0] is fn:
            return hit[1]
        # a memo miss builds the jit wrapper only: XLA compiles at the
        # program's first launch, which ``backend.compiles`` counts (and,
        # traced, times as a ``backend.compile`` span under the launch)
        with _obs.span("backend.program_build", cat="compile") as sp:
            prog = build()
            if sp:
                sp.set(backend=self.name, kind=kind,
                       devices=self.device_count())
        _metrics.counter("backend.programs_built").inc()
        # bounded cache on the process-wide singleton: evict oldest first
        # so callers that churn fresh fn closures cannot grow it without
        # limit (reusing one fn object per cost surface stays the fast
        # path — see the module docstring contract)
        while len(self._programs) >= self.MAX_PROGRAMS:
            self._programs.pop(next(iter(self._programs)))
        # hold a strong ref to fn: keeps id(fn) valid for the cache lifetime
        self._programs[key] = (fn, prog)
        return prog

    def _call(self, fn, cfgs, params):
        return fn(cfgs) if params is None else fn(cfgs, params)

    def _params(self, params):
        """Params on the device: cast on the host (as ``jnp.asarray``
        would) and transferred, so the upload is no program."""
        dtype = np.float64 if self.exact else np.float32
        return self._jax.device_put(
            np.asarray([] if params is None else params, dtype=dtype))

    # -- chunked grid scan --------------------------------------------------- #
    def _decoder(self, cluster: ClusterConditions):
        """The scan programs' decode, made once per program build:
        ``rows(flat) -> (ok, configs)``, the in-grid mask of a span's flat
        row ids and their configurations as a ``ConfigColumns`` view,
        decoded by ``_decode_columns`` (rows past the grid decode as row
        0; the caller masks their costs to inf)."""
        jnp = self.xp
        total = cluster.grid_size()
        metas, sizes = _dim_meta(cluster), _dim_sizes(cluster)

        def rows(flat):
            ok = flat < total
            return ok, ConfigColumns(
                _decode_columns(jnp.where(ok, flat, 0), metas, sizes))
        return rows

    @hot_path("launches one compiled program per grid scan per request",
              folds=2)
    def argmin_grid(self, batch_cost_fn: BatchCostFn,
                    cluster: ClusterConditions,
                    stats: Optional[PlanningStats] = None, *,
                    params=None, chunk_size: int = DEFAULT_CHUNK) -> Result:
        """Scan the whole grid in one jitted program, span by span.

        With D local devices a span is ``D * chunk`` contiguous flat rows,
        ``shard_map``-partitioned so every device reduces its own
        ``chunk``-row shard to a ``(best_cost, best_flat)`` pair and the
        cross-shard fold runs inside the program; with D == 1 a span is
        one chunk.  A grid of more than one span loops over its spans on
        the device (``_over_spans``), so the host launches once and syncs
        once per call.  First-strict-minimum tie-breaking matches the
        numpy backend everywhere: the first min within a shard, the
        lowest (= lowest-rows) device across shards, and the carried
        (lower-rows) best across spans."""
        jax, jnp = self._jax, self.xp
        stats = stats if stats is not None else PlanningStats()
        total = cluster.grid_size()
        D = self.device_count()
        chunk = int(min(chunk_size, _pad_multiple(total, D) // D))
        span = chunk * D
        n_spans = -(-total // span)
        has_params = params is not None

        def build():
            rows = self._decoder(cluster)

            def shard_body(flat, p):
                ok, cfgs = rows(flat)
                costs = self._call(batch_cost_fn, cfgs,
                                   p if has_params else None)
                return _first_min(jnp.where(ok, costs, jnp.inf), flat, 0)

            if D == 1:
                def scan_span(lo, p):
                    return shard_body(lo + jnp.arange(chunk), p)
            else:
                PS = jax.sharding.PartitionSpec
                shard = self._shard_map(
                    lambda flat, p: tuple(r[None]
                                          for r in shard_body(flat, p)),
                    mesh=self._plan_mesh(),
                    in_specs=(PS("plan"), PS()),
                    out_specs=(PS("plan"), PS("plan")))

                def scan_span(lo, p):
                    # shards hold ascending contiguous flat ranges, so the
                    # first minimum over the (D,) per-shard bests (lowest
                    # device = lowest rows) is the globally first strict
                    # minimum of the span
                    cs, fs = shard(lo + jnp.arange(span), p)
                    return _first_min(cs, fs, 0)
            return jax.jit(_named(_over_spans(scan_span, n_spans, span),
                                  "plan_scan"))

        with self._scope():
            prog = self._program("scan", batch_cost_fn, cluster,
                                 (chunk, has_params, D), build)
            p = self._params(params)
            with _obs.span("backend.launch", cat="dispatch") as sp:
                c, f = prog(0, p)           # async dispatch: no host sync
                if sp:
                    sp.set(kind="scan", Qpad=1, chunk=chunk, launches=1,
                           spans=n_spans)
            _metrics.counter("backend.launches").inc()
            _metrics.counter("backend.scan_spans").inc(n_spans)
            stats.configs_explored += total
            cost = np.asarray(c)                            # one sync
            flat = np.asarray(f)
        best_cost = float(cost)
        if math.isinf(best_cost):
            return None, math.inf
        return _decode_flat(cluster, flat), best_cost

    @hot_path("launches one compiled program per stacked grid scan per "
              "flush", folds=3)  # params-normalizing asarray + 2 outputs
    def argmin_grid_many_async(self, batch_cost_fn: BatchCostFn,
                               cluster: ClusterConditions,
                               params_many, *,
                               stats: Optional[PlanningStats] = None,
                               chunk_size: int = DEFAULT_CHUNK
                               ) -> Callable[[], List[Result]]:
        """Launch the stacked scan for Q requests and return a zero-arg
        ``finalize`` closure that performs the single host sync + decode.

        One vmapped jitted program per stacked scan: config enumeration
        is hoisted out of the ``jax.vmap`` (every lane scans the same grid
        rows), only the cost evaluation is mapped over the ``(Q, P)``
        params axis.  With D devices each span is ``D * chunk`` rows,
        ``shard_map``-partitioned so every device reduces its shard to a
        per-request ``(best_cost, best_flat)`` row and the cross-shard
        fold (first minimum = lowest device = lowest rows) runs inside
        the program; a grid of more than one span loops over its spans
        on the device (``_over_spans``).  Chunk sizing is ``_many_chunk``
        (floored shards + explicit live-memory cap — the old
        ``chunk_size // Q`` floored to tiny chunks for large Q); Q is
        padded to even so the compiled shape set is halved at <= one
        wasted lane.  Nothing syncs until ``finalize()``, so the broker
        can dispatch wave N and keep enumerating wave N+1 while it
        runs."""
        jax, jnp = self._jax, self.xp
        stats = stats if stats is not None else PlanningStats()
        pm = np.asarray(params_many, dtype=np.float64)
        Q, P = pm.shape
        if Q == 0:
            return lambda: []
        total = cluster.grid_size()
        D = self.device_count()
        Qpad = _pad_even(Q)
        chunk = _many_chunk(total, Qpad, D, chunk_size)
        span = chunk * D
        n_spans = -(-total // span)

        def build():
            rows = self._decoder(cluster)

            def shard_body(flat, p):
                ok, cfgs = rows(flat)
                costs = jax.vmap(lambda q: batch_cost_fn(cfgs, q))(p)
                costs = jnp.where(ok[None, :], costs, jnp.inf)  # (Q, rows)
                return _first_min(costs, flat[None, :], 1)

            if D == 1:
                def scan_span(lo, p):
                    return shard_body(lo + jnp.arange(chunk), p)
            else:
                PS = jax.sharding.PartitionSpec
                shard = self._shard_map(
                    lambda flat, p: tuple(r[None]
                                          for r in shard_body(flat, p)),
                    mesh=self._plan_mesh(),
                    in_specs=(PS("plan"), PS()),
                    out_specs=(PS("plan"), PS("plan")))

                def scan_span(lo, p):
                    cs, fs = shard(lo + jnp.arange(span), p)  # (D, Qpad)
                    # first minimum over the device axis = lowest device =
                    # lowest flat rows: the strict-< tie-break per request
                    return _first_min(cs, fs, 0)
            return jax.jit(_named(_over_spans(scan_span, n_spans, span),
                                  f"plan_scan_many_w{Qpad}"))

        with self._scope():
            prog = self._program("scan_many", batch_cost_fn, cluster,
                                 (chunk, Qpad, P, D), build)
            p = self._params(np.pad(pm, ((0, Qpad - Q), (0, 0)),
                                    mode="edge"))
            with _obs.span("backend.launch", cat="dispatch") as sp:
                c, f = prog(0, p)           # async dispatch: no host sync
                if sp:
                    sp.set(kind="scan_many", Qpad=Qpad, chunk=chunk,
                           launches=1, spans=n_spans)
            _metrics.counter("backend.launches").inc()
            _metrics.counter("backend.scan_spans").inc(n_spans)
            stats.configs_explored += Q * total

        def finalize() -> List[Result]:
            costs = np.asarray(c)[:Q]                       # one sync
            flats = np.asarray(f)[:Q]
            out: List[Result] = []
            for q in range(Q):
                cq = float(costs[q])
                if math.isinf(cq):
                    out.append((None, math.inf))
                else:
                    out.append((_decode_flat(cluster, flats[q]), cq))
            return out

        return finalize

    def argmin_grid_many(self, batch_cost_fn: BatchCostFn,
                         cluster: ClusterConditions,
                         params_many, *,
                         stats: Optional[PlanningStats] = None,
                         chunk_size: int = DEFAULT_CHUNK) -> List[Result]:
        """Synchronous stacked scan: dispatch + finalize in one call (see
        ``argmin_grid_many_async`` for the split the broker waves use)."""
        return self.argmin_grid_many_async(
            batch_cost_fn, cluster, params_many, stats=stats,
            chunk_size=chunk_size)()

    # -- fused ensemble climb ------------------------------------------------ #
    def _climb_fn(self, batch_cost_fn: BatchCostFn, grids_np: List[np.ndarray],
                  max_iters: int, has_params: bool):
        """The whole multi-start climb — neighbor generation, batched
        costing, steepest-descent moves, termination — as one traceable
        ``lax.while_loop`` function ``climb(start_idx, p)``.  Jitted
        directly for a single request; ``jax.vmap``-ed over the params
        axis (then jitted) for a stacked request batch."""
        jax, jnp = self._jax, self.xp
        n_dims = len(grids_np)
        grids = [jnp.asarray(g) for g in grids_np]
        sizes = jnp.asarray([len(g) for g in grids_np])
        offs = jnp.asarray(_neighbor_offsets(n_dims))

        def values_of(idx):
            return jnp.stack([grids[d][idx[:, d]]
                              for d in range(n_dims)], axis=1)

        def climb(start_idx, p):
            S = start_idx.shape[0]
            pp = p if has_params else None
            cost0 = self._call(batch_cost_fn, values_of(start_idx), pp)

            def cond(state):
                it, moved, _, _, _ = state
                return moved & (it < max_iters)

            def body(state):
                it, _, cur, cur_cost, n_eval = state
                nbr = cur[:, None, :] + offs[None, :, :]   # (S, 2D, D)
                valid = ((nbr >= 0) & (nbr < sizes)).all(-1)
                flat = nbr.reshape(-1, n_dims)
                safe = jnp.clip(flat, 0, sizes - 1)
                costs = self._call(batch_cost_fn, values_of(safe), pp)
                costs = jnp.where(valid, costs.reshape(S, 2 * n_dims),
                                  jnp.inf)
                j = jnp.argmin(costs, axis=1)
                best_c = jnp.take_along_axis(costs, j[:, None], 1)[:, 0]
                improved = best_c < cur_cost
                step = jnp.take_along_axis(
                    nbr, j[:, None, None], 1)[:, 0, :]
                cur = jnp.where(improved[:, None], step, cur)
                cur_cost = jnp.where(improved, best_c, cur_cost)
                return (it + 1, improved.any(), cur, cur_cost,
                        n_eval + valid.sum(dtype=jnp.int32))

            it, _, cur, cur_cost, n_eval = jax.lax.while_loop(
                cond, body, (jnp.int32(0), jnp.bool_(True),
                             start_idx, cost0, jnp.int32(0)))
            i = jnp.argmin(cur_cost)
            return cur[i], cur_cost[i], n_eval

        return climb

    @hot_path("runs the fused whole-ensemble climb program per request",
              folds=2)
    def hill_climb_ensemble(self, batch_cost_fn: BatchCostFn,
                            cluster: ClusterConditions,
                            starts: Optional[Sequence[Sequence[int]]] = None,
                            stats: Optional[PlanningStats] = None, *,
                            params=None, n_random: int = 0, seed: int = 0,
                            max_iters: int = 100_000) -> Result:
        """One fused-``while_loop`` jitted program for the whole ensemble.
        No per-iteration host sync: this is what makes ensembles of dozens
        of starts cheaper than the numpy 2-start climb (ROADMAP open
        item)."""
        jax, jnp = self._jax, self.xp
        stats = stats if stats is not None else PlanningStats()
        grids_np = grid_arrays(cluster)
        n_dims = len(grids_np)
        cur0 = start_indices(cluster, starts, n_random, seed)
        S = len(cur0)
        has_params = params is not None

        with self._scope():
            prog = self._program(
                "climb", batch_cost_fn, cluster, (S, max_iters, has_params),
                lambda: jax.jit(_named(
                    self._climb_fn(batch_cost_fn, grids_np, max_iters,
                                   has_params), "plan_climb")))
            idx, cost, n_eval = prog(jnp.asarray(cur0), self._params(params))
            idx = np.asarray(idx)
            n_eval = int(n_eval)
        # in-bounds cost evaluations actually performed (the fused loop
        # re-costs converged starts too; that is real work, so count it)
        stats.configs_explored += S + n_eval
        res = tuple(int(grids_np[d][idx[d]]) for d in range(n_dims))
        return res, float(cost)

    @hot_path("runs the vmapped stacked-ensemble climb program per flush",
              folds=4)  # params-normalizing asarray + the 3-site fold
    def hill_climb_ensemble_many_async(self, batch_cost_fn: BatchCostFn,
                                       cluster: ClusterConditions,
                                       params_many, *,
                                       starts=None,
                                       stats: Optional[PlanningStats] = None,
                                       n_random: int = 0, seed: int = 0,
                                       max_iters: int = 100_000
                                       ) -> Callable[[], List[Result]]:
        """Dispatch the stacked ensemble climb and return a zero-arg
        ``finalize`` closure that performs the host sync + decode.

        ONE ``jax.vmap``-ed jitted ``while_loop`` program (starts shared
        across requests, the params axis mapped).  With D devices the
        *request* axis is ``shard_map``-partitioned over the plan mesh —
        Q padded to a multiple of max(2, D) — so each device climbs its
        own request lanes; vmap lanes are independent (no collectives in
        the climb), so per-request trajectories and results are identical
        with the single-device program."""
        jax, jnp = self._jax, self.xp
        stats = stats if stats is not None else PlanningStats()
        pm = np.asarray(params_many, dtype=np.float64)
        Q, P = pm.shape
        if Q == 0:
            return lambda: []
        grids_np = grid_arrays(cluster)
        n_dims = len(grids_np)
        cur0 = start_indices(cluster, starts, n_random, seed)
        S = len(cur0)
        D = self.device_count()
        Qpad = _pad_multiple(Q, max(2, D))

        def build():
            climb = self._climb_fn(batch_cost_fn, grids_np, max_iters, True)
            vm = jax.vmap(climb, in_axes=(None, 0))
            if D == 1:
                return jax.jit(_named(vm, "plan_climb_many"))
            PS = jax.sharding.PartitionSpec
            # check_vma=False: every output is genuinely sharded over
            # the request axis, so the varying-manual-axes check adds
            # nothing here
            return jax.jit(_named(self._shard_map(
                vm, mesh=self._plan_mesh(),
                in_specs=(PS(), PS("plan")),
                out_specs=(PS("plan"), PS("plan"), PS("plan")),
                check_vma=False), "plan_climb_many"))

        with self._scope():
            prog = self._program("climb_many", batch_cost_fn, cluster,
                                 (S, max_iters, Qpad, P, D), build)
            p = self._params(np.pad(pm, ((0, Qpad - Q), (0, 0)),
                                    mode="edge"))
            idx_d, cost_d, n_eval_d = prog(jnp.asarray(cur0), p)

        def finalize() -> List[Result]:
            idx = np.asarray(idx_d)[:Q]
            cost = np.asarray(cost_d)[:Q]
            n_evals = np.asarray(n_eval_d)[:Q]
            stats.configs_explored += Q * S + int(n_evals.sum())
            return [(tuple(int(grids_np[d][idx[q, d]])
                           for d in range(n_dims)), float(cost[q]))
                    for q in range(Q)]

        return finalize

    def hill_climb_ensemble_many(self, batch_cost_fn: BatchCostFn,
                                 cluster: ClusterConditions,
                                 params_many, *,
                                 starts=None,
                                 stats: Optional[PlanningStats] = None,
                                 n_random: int = 0, seed: int = 0,
                                 max_iters: int = 100_000) -> List[Result]:
        """Synchronous stacked climb: dispatch + finalize in one call (see
        ``hill_climb_ensemble_many_async`` for the broker-wave split)."""
        return self.hill_climb_ensemble_many_async(
            batch_cost_fn, cluster, params_many, starts=starts, stats=stats,
            n_random=n_random, seed=seed, max_iters=max_iters)()


PlanBackend = Union[NumpyPlanBackend, JaxPlanBackend]

_SINGLETONS = {}


def have_jax() -> bool:
    """Whether the jax backend can be constructed on this host."""
    return have_backend("jax")


def have_backend(spec: str) -> bool:
    """Whether ``get_backend(spec)`` can be constructed on this host."""
    try:
        get_backend(spec)
        return True
    except ImportError:
        return False


def get_backend(spec: Union[str, PlanBackend, None] = None) -> PlanBackend:
    """Resolve a backend selection: None/"numpy", "jax", "jax_x64" (exact
    x64-scoped jit), "auto" (jax if importable, else numpy), or an
    already-constructed backend instance.
    String selections return process-wide singletons so compiled-program
    caches are shared."""
    if spec is None:
        spec = "numpy"
    if not isinstance(spec, str):
        return spec
    if spec == "auto":
        try:
            return get_backend("jax")
        except ImportError:
            return get_backend("numpy")
    if spec not in _SINGLETONS:
        if spec == "numpy":
            _SINGLETONS[spec] = NumpyPlanBackend()
        elif spec == "jax":
            _SINGLETONS[spec] = JaxPlanBackend()
        elif spec == "jax_x64":
            _SINGLETONS[spec] = JaxPlanBackend(precision="x64")
        else:
            raise ValueError(f"unknown plan backend {spec!r} (expected "
                             "'numpy', 'jax', 'jax_x64' or 'auto')")
    return _SINGLETONS[spec]
