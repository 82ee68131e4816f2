"""Fused cost-scan + argmin Pallas kernels: resource planning as ONE
streaming reduction, and the ``PallasPlanBackend`` that wraps them.

The array backends (planning_backend) made the §VI-B1 exhaustive scan a
chunked array program, but every chunk still *materializes* its cost
vector (and the broker's stacked path a ``(Q, chunk)`` cost matrix) in
main memory before a separate argmin pass reads it back — the last
memory-bound wall in the 10M-config ``scaled_cluster(100_000, 100)``
scan (ROADMAP open item).  The kernels here break it by fusing the three
stages of the scan into one Pallas program per grid block:

    decode     flat row ids -> configuration values, *in-kernel* (affine
               dims by arithmetic, explicit-value dims by compare-select
               over the small value table) — the config array is never
               materialized in HBM, let alone the cost vector
    cost       the caller's batch cost surface ``fn(configs, params)``
               evaluated on the VMEM-resident block (the same traceable
               fn the jax backend jits; infeasible/OOM configs cost inf
               and are masked in-kernel)
    reduce     a streaming argmin: the running ``(best_cost, best_idx)``
               pair is carried across grid blocks in SMEM scalars of the
               whole-array outputs (TPU grids iterate sequentially), with
               strict-``<`` updates in ascending block order so ties
               break to the *first* minimum in ``enumerate_configs``
               order — the scalar loop's tie-breaking contract, preserved
               bit-for-bit

A block is computed as 2-D ``(rows, 128)`` vreg tiles of flat row ids,
decoded into one tile per dimension (a ``ConfigColumns`` view the cost
fn reads as ``configs[:, d]``); per-request params arrive as SMEM
scalars.  Nothing is stacked into an ``(N, n_dims)`` array or gathered
dynamically, which is what lets the TPU compiler accept the kernels
(``tests/test_tpu_compile.py`` compiles them for a v5e chip).

Two scan kernels:

* ``_scan_kernel`` — one request as a 1-D grid over config blocks, or Q
  stacked requests as a 2-D grid over ``(query, block)``: each program
  reads its query's params from SMEM, the block axis is minor, and each
  program reduces its own block of costs, so the broker's stacked flush
  runs with ZERO materialized ``(Q, chunk)`` cost matrix (the jax
  backend's vmap builds one per chunk).  ``build_scan_sharded`` runs the
  same kernel on every device of the plan mesh over its own span.
* ``_scan_many_unrolled_kernel`` — the same stacked scan with the query
  axis unrolled *inside* the block body (config decode shared across all
  Q lanes).  This is the interpret-mode variant: Pallas interpret lowers
  multi-step grids to an XLA loop that executes serially, so the CPU
  path instead bakes one single-block executable per chunk (static
  ``lo0``), dispatches them async, and folds the per-chunk winners with
  ONE host sync — distinct executables run concurrently on XLA:CPU,
  which is what makes the interpret scan *faster* than the jitted jax
  chunk loop and its per-chunk syncs.

plus ``_neighbor_kernel``, the ensemble-climb neighbor-costing step
(§VI-B2): neighbor generation, bounds masking, batched costing of every
±1 neighbor of every start, and the per-start best-neighbor argmin, all
fused into one program per climb iteration.

``PallasPlanBackend`` (``get_backend("pallas")``) wraps them behind the
full ``PlanBackend`` protocol — ``argmin_grid``, ``argmin_grid_many``,
``hill_climb_ensemble``, ``hill_climb_ensemble_many`` — reusing the jax
backend's compiled-program memo (one trace per (cost-fn object, grid,
geometry)).  On non-TPU hosts the kernels run in interpret mode, so
correctness (and the CI backend matrix) is verifiable everywhere; on TPU
the full grid is one ``pallas_call`` with the carried reduction.

Numerics: compute is float32 (like ``get_backend("jax")``), so
``exact = False`` and the planners' float64 commit/fallback applies; the
parity suites pin argmin/tie-break identity on f32-exact cost surfaces.
Flat row ids are int32: grids within one padded block of 2**31
configurations fall back to the inherited jax path (the §VII-C 10M-point
grid is ~200x below that).
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.registry import hot_path
from repro.core.cluster import ClusterConditions, ConfigColumns, PlanningStats
from repro.core.planning_backend import (  # noqa: F401 (re-exported types)
    DEFAULT_CHUNK, BatchCostFn, JaxPlanBackend, Result, _decode_columns,
    _decode_flat, _dim_meta, _dim_sizes, _neighbor_offsets, _pad_even,
    _pad_multiple, _value_of_index, grid_arrays, start_indices)
from repro.obs import get_tracer

_obs = get_tracer()

# int32 flat row ids: grids within one (padded) block of 2**31 configs
# take the jax fallback path so tail-block ids never wrap negative
MAX_FLAT = 1 << 31
# query lanes per unrolled interpret-mode program (bounds trace size)
UNROLL_Q = 64
# the TPU's f32/int32 vreg tile is SUBLANES x LANES; compiled scan blocks
# are whole tiles
SUBLANES, LANES = 8, 128
_INT32_MAX = np.iinfo(np.int32).max


# ----------------------------- in-kernel decode ----------------------------- #
# The decode recipe (``_dim_meta``, ``_decode_columns``) is the one the jax
# backend's scan programs use, shared from ``planning_backend``; here it
# runs on one block's 2-D tile of flat row ids.

def _tile(block: int) -> Tuple[int, int]:
    """The 2-D shape one block of ``block`` flat rows is computed in:
    whole 128-lane rows when ``block`` is a multiple of 128 (compiled
    blocks are whole (8, 128) vreg tiles), else one row (the small blocks
    of interpret-mode tests)."""
    return (block // LANES, LANES) if block % LANES == 0 else (1, block)


def _flat_ids(start, tile):
    """``tile``-shaped int32 flat row ids ``start + r * lanes + l``.  They
    ascend in row-major order, so the lowest id attaining a tile's minimum
    is its first minimum in ``enumerate_configs`` order."""
    r = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile, 1)
    return start + r * tile[1] + lane


# --------------------------- closure hoisting ------------------------------- #
# Pallas kernels cannot capture array constants (a cost fn closing over
# device tables raises "captures constants ... pass them as inputs").
# Tracing the batch cost fn to a jaxpr up front splits it into a pure
# computation plus its hoisted array constants; the builders below feed
# those constants to the kernel as extra (whole-array, VMEM-resident)
# inputs and evaluate the jaxpr on the in-kernel block.  Cost fns built
# from python/numpy scalars (every cost model in this repo) embed them as
# jaxpr literals and hoist zero constants.

def _split_cost_fn(fn: BatchCostFn, tile: Tuple[int, int], n_dims: int,
                   p_width: int, has_params: bool):
    """-> (call(cols, ps, const_vals) -> ``tile``-shaped f32 costs,
    const_ins, const_shapes).

    The fn is traced on a ``ConfigColumns`` of ``tile``-shaped int32
    columns, one per dimension, and on ``p_width`` f32 scalar params: the
    kernels read params from SMEM one scalar at a time, and a cost fn
    indexes ``params[k]`` either way."""
    from jax import core as jax_core
    cols_ex = [jax.ShapeDtypeStruct(tile, jnp.int32)] * n_dims
    ps_ex = [jax.ShapeDtypeStruct((), jnp.float32)] * p_width
    size = tile[0] * tile[1]

    def traced(cols, ps):
        cfgs = ConfigColumns(cols)
        return fn(cfgs, ps) if has_params else fn(cfgs)

    # the jaxpr pre-trace is the kernel-build cost worth seeing in a
    # trace: program assembly around it is cheap python
    with _obs.span("pallas.pretrace", cat="compile") as sp:
        cj = jax.make_jaxpr(traced)(cols_ex, ps_ex)
        if sp:
            sp.set(rows=size, dims=n_dims,
                   params=p_width if has_params else 0)

    def call(cols, ps, const_vals):
        out, = jax_core.eval_jaxpr(cj.jaxpr, const_vals, *cols, *ps)
        # a cost fn that indexes rows of the stacked (N, n_dims) array
        # returns a flat (N,) vector
        return out.astype(jnp.float32).reshape(tile)

    ins, shapes = [], []
    for c in cj.consts:
        arr = jnp.asarray(c)
        shapes.append(arr.shape)
        ins.append(arr.reshape((1,)) if arr.ndim == 0 else arr)
    return call, ins, tuple(shapes)


def _const_specs(const_ins):
    """Whole-array BlockSpecs (constant, grid-arity-agnostic index map)
    for hoisted consts."""
    specs = []
    for arr in const_ins:
        nd = arr.ndim
        specs.append(pl.BlockSpec(arr.shape,
                                  (lambda n: lambda *_: (0,) * n)(nd)))
    return specs


def _const_values(const_refs, shapes):
    return [r[...].reshape(s) for r, s in zip(const_refs, shapes)]


def _smem():
    """Whole-array SMEM spec: params, offsets and the carried scalar
    accumulators (vector memory refuses scalar stores)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ------------------------------ scan kernels -------------------------------- #

def _fold_block(costs, flat, q, cost_acc, idx_acc):
    """Reduce one block and fold it into slot ``q`` of the carried SMEM
    accumulators: the block minimum, then the lowest flat id attaining
    it (first-minimum tie-breaking within the block, with no dynamic
    gather), then a strict-< update — ascending block order makes the
    carried winner the first global minimum in ``enumerate_configs``
    order."""
    c = jnp.min(costs)
    j = jnp.min(jnp.where(costs == c, flat, _INT32_MAX))
    better = c < cost_acc[q]
    idx_acc[q] = jnp.where(better, j, idx_acc[q])
    cost_acc[q] = jnp.where(better, c, cost_acc[q])


def _block_columns(start, tile, metas, sizes, total, masked):
    """-> (flat ids, in-grid mask or None, decoded config columns) of the
    block starting at flat row ``start``; rows past the grid decode as
    row 0 and are masked to inf by the caller."""
    flat = _flat_ids(start, tile)
    ok = flat < total if masked else None
    cols = _decode_columns(jnp.where(ok, flat, 0) if masked else flat,
                           metas, sizes)
    return flat, ok, cols


def _scan_kernel(*refs, cost, shapes, metas, sizes, total, tile, lo0,
                 masked, many, p_width):
    """One grid block: cost rows [start, start + block) and fold them
    into the (best_cost, best_idx) accumulator of the block's query,
    carried in SMEM across the sequential grid.  ``lo0`` is the static
    first row (the interpret path bakes one executable per chunk so
    XLA:CPU runs chunks concurrently; the compiled path runs lo0=0 with
    the full grid), or None to read it from a leading ``(1,)`` SMEM input
    (one executable serves every shard of the sharded scan)."""
    if lo0 is None:
        lo0, refs = refs[0][0], refs[1:]
    params_ref, const_refs, (cost_ref, idx_ref) = \
        refs[0], refs[1:-2], refs[-2:]
    q = pl.program_id(0) if many else 0
    b = pl.program_id(1 if many else 0)

    @pl.when(b == 0)
    def _init():
        cost_ref[q] = jnp.float32(jnp.inf)
        idx_ref[q] = jnp.int32(-1)

    flat, ok, cols = _block_columns(lo0 + b * (tile[0] * tile[1]), tile,
                                    metas, sizes, total, masked)
    ps = [params_ref[q * p_width + k] for k in range(p_width)]
    costs = cost(cols, ps, _const_values(const_refs, shapes))
    if masked:
        costs = jnp.where(ok, costs, jnp.inf)
    _fold_block(costs, flat, q, cost_ref, idx_ref)


def _scan_many_unrolled_kernel(params_ref, *refs, cost, shapes, metas,
                               sizes, total, tile, lo0, nq, masked,
                               p_width):
    """Q stacked requests with the query axis unrolled inside the block
    body: the config block is decoded ONCE and shared by all Q cost
    evaluations (the jax backend hoists enumeration out of its vmap the
    same way).  Interpret-mode variant — every per-query cost op stays a
    top-level op that XLA:CPU can multi-thread."""
    const_refs, (cost_ref, idx_ref) = refs[:-2], refs[-2:]
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        for q in range(nq):
            cost_ref[q] = jnp.float32(jnp.inf)
            idx_ref[q] = jnp.int32(-1)

    flat, ok, cols = _block_columns(lo0 + b * (tile[0] * tile[1]), tile,
                                    metas, sizes, total, masked)
    const_vals = _const_values(const_refs, shapes)
    for q in range(nq):
        ps = [params_ref[q * p_width + k] for k in range(p_width)]
        costs = cost(cols, ps, const_vals)
        if masked:
            costs = jnp.where(ok, costs, jnp.inf)
        _fold_block(costs, flat, q, cost_ref, idx_ref)


def _neighbor_kernel(cur_ref, params_ref, *refs, cost, shapes, metas,
                     sizes_t, n_dims, p_width):
    """The ensemble-climb neighbor-costing step (Algorithm 1's inner
    batch): cost the S current positions and all their 2*n_dims ±1
    neighbors in ONE fused cost evaluation, mask out-of-grid steps to
    inf, and reduce each start's best neighbor (first-minimum
    tie-breaking over the fixed ``_neighbor_offsets`` order) — one
    program per climb step.

    Starts lie on lanes (``cur_ref`` is ``(n_dims, S)``) and neighbor
    slots on sublanes: row 0 is the center, row ``1 + 2d`` the -1 step
    and row ``2 + 2d`` the +1 step of dim d, exactly the
    ``_neighbor_offsets`` order; rows past ``2 * n_dims`` pad the tile
    to whole sublane groups and never win."""
    const_refs = refs[:-3]
    center_ref, best_c_ref, best_j_ref = refs[-3:]
    n_slots = 2 * n_dims
    tile = (_pad_multiple(n_slots + 1, SUBLANES), cur_ref.shape[1])
    slot = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
    valid = None
    cols = []
    for d in range(n_dims):
        step = jnp.where(slot == 1 + 2 * d, -1,
                         jnp.where(slot == 2 + 2 * d, 1, 0))
        idx = cur_ref[d:d + 1, :] + step                   # (rows, S)
        ok = (idx >= 0) & (idx < sizes_t[d])
        valid = ok if valid is None else valid & ok
        cols.append(_value_of_index(jnp.clip(idx, 0, sizes_t[d] - 1),
                                    metas[d]))
    ps = [params_ref[k] for k in range(p_width)]
    costs = cost(cols, ps, _const_values(const_refs, shapes))
    center_ref[...] = costs[0:1, :]
    is_nbr = (slot >= 1) & (slot <= n_slots)
    ncosts = jnp.where(is_nbr & valid, costs, jnp.inf)
    best = jnp.min(ncosts, axis=0, keepdims=True)
    best_c_ref[...] = best
    best_j_ref[...] = jnp.min(
        jnp.where(is_nbr & (ncosts == best), slot - 1, n_slots),
        axis=0, keepdims=True)


# ------------------------------ call builders ------------------------------- #

def _scan_call(fn: BatchCostFn, cluster: ClusterConditions, *, block: int,
               nb: int, nq: int, lo0: Optional[int], has_params: bool,
               p_width: int, masked: bool, interpret: bool):
    """The fused scan ``pallas_call`` over ``nb`` blocks starting at flat
    row ``lo0`` (static), or at a traced offset passed as a leading
    ``(1,)`` input when ``lo0`` is None, plus its hoisted const inputs.
    Params arrive flat, ``(max(1, nq) * p_width,)``; both outputs are
    ``(max(1, nq),)``.

    ``nq == 0``: one request, 1-D grid of ``nb`` blocks.
    ``nq > 0``: Q stacked requests as a 2-D grid over (query, block) —
    block axis minor so each row's carried accumulator completes before
    the next row starts.  No (Q, chunk) cost matrix exists anywhere:
    every program reduces its own block of costs in VMEM."""
    tile = _tile(block)
    cost, const_ins, shapes = _split_cost_fn(
        fn, tile, cluster.n_dims, p_width, has_params or nq > 0)
    many = nq > 0
    kernel = functools.partial(
        _scan_kernel, cost=cost, shapes=shapes, metas=_dim_meta(cluster),
        sizes=_dim_sizes(cluster), total=cluster.grid_size(), tile=tile,
        lo0=lo0, masked=masked, many=many, p_width=p_width)
    rows = max(1, nq)
    call = pl.pallas_call(
        kernel,
        grid=(nq, nb) if many else (nb,),
        in_specs=[_smem()] * (2 if lo0 is None else 1)
        + _const_specs(const_ins),
        out_specs=[_smem(), _smem()],
        out_shape=[jax.ShapeDtypeStruct((rows,), jnp.float32),
                   jax.ShapeDtypeStruct((rows,), jnp.int32)],
        interpret=interpret,
    )
    return call, const_ins


@hot_path("builds the fused scan program the per-chunk dispatch loop runs")
def build_scan(fn: BatchCostFn, cluster: ClusterConditions, *, block: int,
               nb: int, nq: int, lo0: int, has_params: bool, p_width: int,
               masked: bool, interpret: bool):
    """Jitted fused scan ``scan(params) -> (costs, idx)`` over ``nb``
    blocks starting at static flat row ``lo0`` (see ``_scan_call``)."""
    call, const_ins = _scan_call(
        fn, cluster, block=block, nb=nb, nq=nq, lo0=lo0,
        has_params=has_params, p_width=p_width, masked=masked,
        interpret=interpret)
    return jax.jit(lambda p: call(p, *const_ins))


@hot_path("builds the sharded scan program one dispatch spreads over the mesh")
def build_scan_sharded(fn: BatchCostFn, cluster: ClusterConditions, *,
                       block: int, nb_shard: int, n_dev: int, nq: int,
                       has_params: bool, p_width: int, mesh,
                       interpret: bool):
    """Jitted fused scan ``scan(params) -> (costs, idx)`` over the whole
    grid for ``max(1, nq)`` requests, partitioned across ``n_dev``
    devices: each device runs the SAME single executable over its own
    ``nb_shard * block``-row span (its start offset arriving as a traced
    scalar through ``shard_map``), carrying its per-shard (best_cost,
    best_idx) accumulators exactly like the unsharded kernel.  The
    cross-shard fold — ``jnp.argmin`` over the per-shard bests of each
    request, first minimum = lowest device = lowest flat rows (spans are
    contiguous and ascending) — happens inside the program, so the result
    is bit-identical to the single-device scan and ONE host sync reads it
    back.  Every block is masked (``flat < total``) because one uniform
    executable must also cover the ragged last shard."""
    call, const_ins = _scan_call(
        fn, cluster, block=block, nb=nb_shard, nq=nq, lo0=None,
        has_params=has_params, p_width=p_width, masked=True,
        interpret=interpret)
    rows = max(1, nq)
    PS = jax.sharding.PartitionSpec
    # check_vma=False: pallas_call has no varying-manual-axes rule, and
    # both outputs are genuinely sharded over "plan" anyway
    shard = shard_map(lambda off, p: call(off, p, *const_ins), mesh=mesh,
                      in_specs=(PS("plan"), PS()),
                      out_specs=(PS("plan"), PS("plan")),
                      check_vma=False)
    offs = jnp.arange(n_dev, dtype=jnp.int32) * (nb_shard * block)

    def run(p):
        cs, fs = shard(offs, p)
        cs, fs = cs.reshape(n_dev, rows), fs.reshape(n_dev, rows)
        k = jnp.argmin(cs, axis=0)[None]       # first min: lowest device
        return (jnp.take_along_axis(cs, k, 0)[0],
                jnp.take_along_axis(fs, k, 0)[0])

    return jax.jit(run)


@hot_path("builds the stacked scan program a flush runs per block chunk")
def build_scan_many_unrolled(fn: BatchCostFn, cluster: ClusterConditions, *,
                             block: int, nb: int, nq: int, lo0: int,
                             p_width: int, masked: bool, interpret: bool):
    """Jitted stacked scan with the query axis unrolled in the body:
    ``scan(params) -> ((Q,) costs, (Q,) idx)``."""
    tile = _tile(block)
    cost, const_ins, shapes = _split_cost_fn(
        fn, tile, cluster.n_dims, p_width, True)
    kernel = functools.partial(
        _scan_many_unrolled_kernel, cost=cost, shapes=shapes,
        metas=_dim_meta(cluster), sizes=_dim_sizes(cluster),
        total=cluster.grid_size(), tile=tile, lo0=lo0, nq=nq,
        masked=masked, p_width=p_width)
    call = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[_smem()] + _const_specs(const_ins),
        out_specs=[_smem(), _smem()],
        out_shape=[jax.ShapeDtypeStruct((nq,), jnp.float32),
                   jax.ShapeDtypeStruct((nq,), jnp.int32)],
        interpret=interpret,
    )
    return jax.jit(lambda p: call(p, *const_ins))


@hot_path("builds the neighbor-step program the climb loop runs per iteration")
def build_neighbor_step(fn: BatchCostFn, cluster: ClusterConditions, *,
                        n_starts: int, has_params: bool, p_width: int,
                        interpret: bool):
    """Jitted ``step(cur_idx_t, params) -> (center, best_cost, best_j)``
    over the ``(n_dims, S)`` transposed start indices; every output is
    ``(1, S)``."""
    n_dims = cluster.n_dims
    tile = (_pad_multiple(2 * n_dims + 1, SUBLANES), n_starts)
    cost, const_ins, shapes = _split_cost_fn(
        fn, tile, n_dims, p_width, has_params)
    kernel = functools.partial(
        _neighbor_kernel, cost=cost, shapes=shapes, metas=_dim_meta(cluster),
        sizes_t=_dim_sizes(cluster), n_dims=n_dims, p_width=p_width)
    row = pl.BlockSpec((1, n_starts), lambda: (0, 0))
    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((n_dims, n_starts), lambda: (0, 0)),
                  _smem()] + _const_specs(const_ins),
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, n_starts), jnp.float32),
                   jax.ShapeDtypeStruct((1, n_starts), jnp.float32),
                   jax.ShapeDtypeStruct((1, n_starts), jnp.int32)],
        interpret=interpret,
    )
    return jax.jit(lambda cur_t, p: call(cur_t, p, *const_ins))


# ------------------------------ the backend --------------------------------- #

class PallasPlanBackend(JaxPlanBackend):
    """``PlanBackend`` over the fused scan+argmin kernels.

    Inherits the jax backend's compiled-program memo (keyed by cost-fn
    object + grid + geometry, so recurring jobs trace once) and its
    float32 numerics (``exact = False``: planners re-commit winners
    through scalar float64, exactly as for ``get_backend("jax")``).

    Geometry: on TPU one ``pallas_call`` covers the whole grid —
    ``block`` rows per program (default 32K, a (256, 128) tile of 128 KB
    per f32 temporary, comfortably inside the ~16 MB VMEM even for cost
    surfaces with a dozen live intermediates; smaller grids round up to
    whole (8, 128) tiles), grid steps iterating sequentially with the
    argmin accumulator carried in SMEM.  In
    interpret mode (any non-TPU host) multi-step grids would lower to a
    single-threaded XLA loop, so the wrapper instead dispatches one
    single-block program per ``block``-row chunk (default 2M rows),
    keeps every per-chunk result on device, and folds them with ONE host
    sync — measurably faster than the jitted jax scan, which syncs once
    per chunk.  ``many_variant`` selects the stacked-scan kernel: the
    2-D (query, block) grid (TPU default) or the query-unrolled block
    body (interpret default); "grid2d"/"unrolled" force one for tests.

    Multi-device sharding (>1 plan devices, see ``launch.mesh``): the
    per-chunk single-block executables of the interpret paths round-robin
    over the plan mesh — params are pre-placed on every device so chunk i
    dispatches on device ``i % D``, and the per-chunk winners hop back to
    device 0 (async copies) before the single stacked fold, which stays
    the one host sync.  The compiled paths instead build ONE sharded
    executable (``build_scan_sharded``), single-request and stacked
    alike: per-device offsets travel through ``shard_map`` and the
    cross-shard fold runs in-program.
    ``shard_variant`` forces a strategy ("roundrobin"/"shardmap"/"off");
    "auto" picks round-robin under interpret, shard_map when compiled.
    Neither changes results: spans stay contiguous/ascending so the fold
    is still first-strict-minimum in ``enumerate_configs`` order.
    """

    def __init__(self, *, block: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 many_variant: str = "auto",
                 devices: Optional[int] = None,
                 shard_variant: str = "auto"):
        super().__init__(precision="float32", devices=devices)
        self.name = "pallas"
        self.interpret = (jax.default_backend() != "tpu") \
            if interpret is None else bool(interpret)
        self.block = int(block) if block else \
            ((1 << 21) if self.interpret else (1 << 15))
        if many_variant not in ("auto", "grid2d", "unrolled"):
            raise ValueError(f"unknown many_variant {many_variant!r}")
        self.many_variant = many_variant
        if shard_variant not in ("auto", "roundrobin", "shardmap", "off"):
            raise ValueError(f"unknown shard_variant {shard_variant!r}")
        self.shard_variant = shard_variant

    # -- helpers ------------------------------------------------------------- #

    def _use_unrolled(self) -> bool:
        if self.many_variant == "auto":
            return self.interpret
        return self.many_variant == "unrolled"

    def _shard_mode(self) -> str:
        """Resolved multi-device dispatch strategy.  "roundrobin" spreads
        the per-chunk executables over the mesh (interpret default —
        distinct executables already dispatch async); "shardmap" runs one
        sharded executable with traced per-device offsets (compiled
        default; forcible under interpret so CI covers the kernel); "off"
        is the single-device geometry."""
        if self.device_count() == 1 or self.shard_variant == "off":
            return "off"
        if self.shard_variant == "auto":
            return "roundrobin" if self.interpret else "shardmap"
        if self.shard_variant == "roundrobin" and not self.interpret:
            return "shardmap"  # per-chunk executables only exist interpreted
        return self.shard_variant

    def _scan_devices(self):
        """Devices the round-robin chunk dispatch cycles over — the plan
        mesh's devices, in mesh (= flat-row) order."""
        return jax.local_devices()[:self.device_count()]

    def _params32(self, params, p_width: int) -> jnp.ndarray:
        p = np.zeros(p_width, dtype=np.float32)
        if params is not None:
            arr = np.asarray(params, dtype=np.float32).ravel()
            p[:arr.size] = arr
        return jnp.asarray(p)

    def _block_rows(self, total: int) -> int:
        """Rows per kernel block for a ``total``-row grid: compiled blocks
        are whole (8, 128) tiles, rows past the grid masked to inf."""
        block = int(min(self.block, total))
        return block if self.interpret else \
            _pad_multiple(block, SUBLANES * LANES)

    def _fits_int32(self, total: int, block: int) -> bool:
        """int32 row ids: the padded tail blocks of every shard reach up
        to ``total + D * block - 1``, which must not wrap negative."""
        return total <= MAX_FLAT - block * self.device_count()

    @staticmethod
    def _result(cluster: ClusterConditions, flat: int, cost: float) -> Result:
        if flat < 0 or math.isinf(cost):
            return None, math.inf
        grids = grid_arrays(cluster)
        shape = tuple(len(g) for g in grids)
        return _decode_flat(grids, shape, flat), float(cost)

    # -- fused grid scan ------------------------------------------------------ #

    @hot_path("dispatches one fused kernel program per block chunk per "
              "request", folds=4)
    def argmin_grid(self, batch_cost_fn: BatchCostFn,
                    cluster: ClusterConditions,
                    stats: Optional[PlanningStats] = None, *,
                    params=None, chunk_size: int = DEFAULT_CHUNK) -> Result:
        """Exhaustive scan as the fused decode+cost+argmin kernel; first
        strict minimum in ``enumerate_configs`` order, (None, inf) when
        every configuration costs inf."""
        stats = stats if stats is not None else PlanningStats()
        total = cluster.grid_size()
        if total == 0:
            return None, math.inf
        block = self._block_rows(total)
        if not self._fits_int32(total, block):
            return super().argmin_grid(batch_cost_fn, cluster, stats,
                                       params=params, chunk_size=chunk_size)
        has_params = params is not None
        p_width = max(1, 0 if params is None else np.size(params))
        p = self._params32(params, p_width)
        stats.configs_explored += total
        mode = self._shard_mode()

        if self.interpret and mode != "shardmap":
            # one single-block executable per chunk, lo baked statically:
            # distinct executables dispatch async and run CONCURRENTLY on
            # XLA:CPU (a multi-step interpret grid would serialize), with
            # one host sync folding the per-chunk winners at the end.
            # With >1 plan devices the chunks round-robin over the mesh
            # (params pre-placed per device; winners hop back to device 0
            # as async copies before the fold — same single sync).
            devs = self._scan_devices()
            rr = mode == "roundrobin" and len(devs) > 1
            ps = [jax.device_put(p, d) for d in devs] if rr else [p]
            outs = []
            for i, lo in enumerate(range(0, total, block)):
                tail = lo + block > total
                prog = self._program(
                    "pscan", batch_cost_fn, cluster,
                    (block, 1, 0, lo, has_params, p_width, tail, True),
                    lambda lo=lo, t=tail: build_scan(
                        batch_cost_fn, cluster, block=block, nb=1, nq=0,
                        lo0=lo, has_params=has_params, p_width=p_width,
                        masked=t, interpret=True))
                outs.append(prog(ps[i % len(ps)]))
            if rr:
                d0 = devs[0]
                outs = [(jax.device_put(c, d0), jax.device_put(f, d0))
                        for c, f in outs]
            costs = np.asarray(jnp.stack([c for c, _ in outs]))[:, 0]
            flats = np.asarray(jnp.stack([f for _, f in outs]))[:, 0]
            k = int(np.argmin(costs))         # first min: lowest-lo chunk
            return self._result(cluster, int(flats[k]), float(costs[k]))

        prog = self._compiled_scan(batch_cost_fn, cluster, block, 0,
                                   has_params, p_width, mode)
        c, f = prog(p)                        # one sync: float()/int()
        return self._result(cluster, int(f[0]), float(c[0]))

    def _compiled_scan(self, batch_cost_fn: BatchCostFn,
                       cluster: ClusterConditions, block: int, nq: int,
                       has_params: bool, p_width: int, mode: str):
        """The one-dispatch scan program over the whole grid for
        ``max(1, nq)`` requests: a sequential grid on one device, or with
        ``mode == "shardmap"`` one sharded executable whose per-device
        offsets travel through ``shard_map`` and whose cross-shard fold
        runs in-program."""
        total = cluster.grid_size()
        if mode == "shardmap":
            D = self.device_count()
            nbs = -(-total // (block * D))    # blocks per shard
            return self._program(
                "pscan_sh", batch_cost_fn, cluster,
                (block, nbs, D, nq, has_params, p_width, self.interpret),
                lambda: build_scan_sharded(
                    batch_cost_fn, cluster, block=block, nb_shard=nbs,
                    n_dev=D, nq=nq, has_params=has_params, p_width=p_width,
                    mesh=self._plan_mesh(), interpret=self.interpret))
        nb = -(-total // block)
        return self._program(
            "pscan_many" if nq else "pscan", batch_cost_fn, cluster,
            (block, nb, nq, 0, has_params, p_width, True, self.interpret),
            lambda: build_scan(batch_cost_fn, cluster, block=block, nb=nb,
                               nq=nq, lo0=0, has_params=has_params,
                               p_width=p_width, masked=True,
                               interpret=self.interpret))

    @hot_path("dispatches the stacked fused-kernel scan per flush",
              folds=5)  # params asarray + 2-site fold per many variant
    def argmin_grid_many_async(self, batch_cost_fn: BatchCostFn,
                               cluster: ClusterConditions,
                               params_many, *,
                               stats: Optional[PlanningStats] = None,
                               chunk_size: int = DEFAULT_CHUNK):
        """Stacked scan for Q requests sharing one cost fn and grid —
        the (Q, P) params form as a 2-D grid over (query, block) (or the
        query-unrolled interpret variant); per-request results identical
        to Q sequential ``argmin_grid`` calls.  Like the jax backend, Q
        is padded to even (last row repeated, results sliced off), so a
        session whose flush-group sizes fluctuate compiles half as many
        distinct batch shapes at <= one wasted lane.

        Dispatch/finalize split (see ``JaxPlanBackend``): this method
        only dispatches the kernels — the returned zero-arg finalize does
        the single host sync and decode, so a double-buffered broker
        flush can keep enumerating while the wave runs.  Round-robin
        device dispatch applies to the per-chunk unrolled path exactly as
        in ``argmin_grid``; the compiled 2-D grid path stays one program
        (its per-query carried accumulators are already a single
        dispatch), sharded over the plan mesh like ``argmin_grid``'s."""
        stats = stats if stats is not None else PlanningStats()
        pm = np.asarray(params_many, dtype=np.float64)
        Q, P = pm.shape
        if Q == 0:
            return lambda: []
        total = cluster.grid_size()
        if total == 0:
            res = [(None, math.inf)] * Q
            return lambda: res
        block = self._block_rows(total)
        if not self._fits_int32(total, block):
            return super().argmin_grid_many_async(batch_cost_fn, cluster,
                                                  pm, stats=stats,
                                                  chunk_size=chunk_size)
        if Q > UNROLL_Q and self._use_unrolled():
            fins = [self.argmin_grid_many_async(
                batch_cost_fn, cluster, pm[lo:lo + UNROLL_Q], stats=stats,
                chunk_size=chunk_size) for lo in range(0, Q, UNROLL_Q)]
            return lambda: [r for fin in fins for r in fin()]
        p_width = max(1, P)
        Qpad = _pad_even(Q)
        pmp = np.pad(pm, ((0, Qpad - Q), (0, 0)), mode="edge")
        p = jnp.asarray(pmp.astype(np.float32).reshape(-1)) if P else \
            jnp.zeros(Qpad, dtype=jnp.float32)
        stats.configs_explored += Q * total

        if self._use_unrolled():
            devs = self._scan_devices()
            rr = self._shard_mode() != "off" and len(devs) > 1
            ps = [jax.device_put(p, d) for d in devs] if rr else [p]
            outs = []
            for i, lo in enumerate(range(0, total, block)):
                tail = lo + block > total
                prog = self._program(
                    "pscan_many_u", batch_cost_fn, cluster,
                    (block, 1, Qpad, lo, p_width, tail, self.interpret),
                    lambda lo=lo, t=tail: build_scan_many_unrolled(
                        batch_cost_fn, cluster, block=block, nb=1,
                        nq=Qpad, lo0=lo, p_width=p_width, masked=t,
                        interpret=self.interpret))
                outs.append(prog(ps[i % len(ps)]))
            if rr:
                d0 = devs[0]
                outs = [(jax.device_put(c, d0), jax.device_put(f, d0))
                        for c, f in outs]

            def finalize() -> List[Result]:
                costs = np.asarray(jnp.stack([c for c, _ in outs]))[:, :Q]
                flats = np.asarray(jnp.stack([f for _, f in outs]))[:, :Q]
                k = np.argmin(costs, axis=0)  # first min: lowest-lo chunk
                return [self._result(cluster, int(flats[k[q], q]),
                                     float(costs[k[q], q]))
                        for q in range(Q)]
            return finalize

        prog = self._compiled_scan(batch_cost_fn, cluster, block, Qpad,
                                   True, p_width, self._shard_mode())
        c, f = prog(p)

        def finalize() -> List[Result]:
            costs = np.asarray(c)[:Q]
            flats = np.asarray(f)[:Q]
            return [self._result(cluster, int(flats[q]), float(costs[q]))
                    for q in range(Q)]
        return finalize

    # -- ensemble climb on the fused neighbor step ---------------------------- #

    @hot_path("runs the fused neighbor-step kernel once per climb iteration")
    def hill_climb_ensemble(self, batch_cost_fn: BatchCostFn,
                            cluster: ClusterConditions,
                            starts: Optional[Sequence[Sequence[int]]] = None,
                            stats: Optional[PlanningStats] = None, *,
                            params=None, n_random: int = 0, seed: int = 0,
                            max_iters: int = 100_000) -> Result:
        """Multi-start steepest descent with the per-iteration neighbor
        batch (generation, masking, costing, per-start argmin) fused into
        one kernel call; moves and termination mirror the numpy backend,
        so trajectories are identical on f32-exact cost surfaces."""
        stats = stats if stats is not None else PlanningStats()
        grids_np = grid_arrays(cluster)
        n_dims = len(grids_np)
        sizes = np.asarray([len(g) for g in grids_np], dtype=np.int64)
        cur = np.asarray(start_indices(cluster, starts, n_random, seed))
        S = len(cur)
        offs = _neighbor_offsets(n_dims)
        has_params = params is not None
        p_width = max(1, 0 if params is None else np.size(params))
        p = self._params32(params, p_width)
        prog = self._program(
            "pnbr", batch_cost_fn, cluster,
            (S, has_params, p_width, self.interpret),
            lambda: build_neighbor_step(
                batch_cost_fn, cluster, n_starts=S, has_params=has_params,
                p_width=p_width, interpret=self.interpret))

        cur_cost = np.full(S, np.inf)
        for it in range(max_iters):
            center, best_c, best_j = prog(
                jnp.asarray(cur.T, dtype=jnp.int32), p)
            # plan-lint: allow(host-sync): the climb is host-driven — each fused neighbor step must land before the move/stop decision; in-kernel while_loop fusion is the ROADMAP follow-up
            center = np.asarray(center, dtype=np.float64)[0]
            best_c = np.asarray(best_c, dtype=np.float64)[0]  # plan-lint: allow(host-sync): same per-iteration fold as the line above
            best_j = np.asarray(best_j)[0]
            nbr = cur[:, None, :] + offs[None, :, :]
            valid = ((nbr >= 0) & (nbr < sizes)).all(-1)
            stats.configs_explored += S + int(valid.sum())
            cur_cost = center
            improved = best_c < center        # strict <: Algorithm 1 stop
            if not improved.any():
                break
            step = np.take_along_axis(
                nbr, best_j[:, None, None], 1)[:, 0, :]
            cur[improved] = step[improved]
            cur_cost[improved] = best_c[improved]

        i = int(np.argmin(cur_cost))
        res = tuple(int(grids_np[d][cur[i, d]]) for d in range(n_dims))
        return res, float(cur_cost[i])

    @hot_path("drives one host climb per stacked request in a flush")
    def hill_climb_ensemble_many(self, batch_cost_fn: BatchCostFn,
                                 cluster: ClusterConditions,
                                 params_many, *,
                                 starts=None,
                                 stats: Optional[PlanningStats] = None,
                                 n_random: int = 0, seed: int = 0,
                                 max_iters: int = 100_000) -> List[Result]:
        """Q climbs sharing one fn/grid/start set: the per-request climb
        runs once per request (the neighbor-step program is traced once
        and reused across all Q), trivially identical to the per-request
        path."""
        pm = np.asarray(params_many, dtype=np.float64)
        return [self.hill_climb_ensemble(
            batch_cost_fn, cluster, starts, stats, params=pm[q],
            n_random=n_random, seed=seed, max_iters=max_iters)
            for q in range(pm.shape[0])]

    def hill_climb_ensemble_many_async(self, *args, **kwargs):
        """The pallas climb is host-driven — every fused neighbor step
        syncs before the move decision — so there is nothing to leave in
        flight: run eagerly, return the results as a finalized closure."""
        res = self.hill_climb_ensemble_many(*args, **kwargs)
        return lambda: res
