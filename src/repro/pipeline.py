"""GPipe pipeline parallelism over a mesh axis, via shard_map + ppermute.

The layer stack (L, ...) is split into ``n_stages`` contiguous stages
sharded over the pipeline mesh axis (canonically "pod": cross-pod ICI is
the slow link, and pipelining hides it behind microbatch compute — the
textbook reason to pipeline *across* pods and keep TP/DP *inside* a pod).

Schedule: classic GPipe fill-drain over T = n_micro + n_stages - 1 ticks.
Each tick every stage (a) runs its layers on its current microbatch,
(b) ppermutes the activation to the next stage.  Bubble fraction =
(n_stages - 1) / T.  The backward pass needs no bespoke code: autodiff of
``ppermute`` is the reverse permute, so jax.grad through this function IS
the GPipe backward schedule.

This composes with the in-stage TP/SP/FSDP plans (the body_fn runs under
the same mesh; its own constraints apply within the stage).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def gpipe_apply(params_stacked: Any, x, body_fn: Callable, *, mesh,
                stage_axis: str = "pod", n_micro: int,
                data_axes=("data",)) -> jnp.ndarray:
    """Run a homogeneous layer stack as a GPipe pipeline.

    params_stacked: pytree with leading layer dim L (L % n_stages == 0)
    x:              (B, S, d) activations (B % n_micro == 0)
    body_fn(stage_params, x) -> x  — applies the stage's layers (it may
                                     itself lax.scan over the local layers)
    Returns (B, S, d) with identical semantics to sequentially applying all
    L layers."""
    n_stages = mesh.shape[stage_axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    leaves = jax.tree_util.tree_leaves(params_stacked)
    L = leaves[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)

    # (L, ...) -> (n_stages, L/S, ...): stage dim sharded over stage_axis
    def restage(a):
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    staged = jax.tree_util.tree_map(restage, params_stacked)
    xs = x.reshape(n_micro, mb, *x.shape[1:])

    T = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    p_specs = jax.tree_util.tree_map(
        lambda a: P(stage_axis, *([None] * (a.ndim - 1))), staged)
    d_axes = tuple(a for a in data_axes if a in mesh.shape)
    bspec = d_axes if len(d_axes) != 1 else d_axes[0]

    def stage_program(stage_params, xs_local):
        # stage_params: (1, L/S, ...) local slice;  xs_local: (n_micro, mb, ...)
        sp = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        stage = jax.lax.axis_index(stage_axis)
        zero = jnp.zeros_like(xs_local[0])

        def tick(carry, t):
            state, out_acc = carry
            # stage 0 ingests microbatch t (clipped; masked when t >= n_micro)
            feed = jax.lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, n_micro - 1), axis=0,
                keepdims=False)
            x_in = jnp.where(stage == 0, feed, state)
            y = body_fn(sp, x_in)
            active = (t >= stage) & (t < stage + n_micro)
            y = jnp.where(active, y, zero)
            # last stage banks its finished microbatch (index t - (S-1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            bank = (stage == n_stages - 1) & (t >= n_stages - 1)
            out_acc = jax.lax.dynamic_update_slice(
                out_acc,
                jnp.where(bank, y, jax.lax.dynamic_index_in_dim(
                    out_acc, out_idx, axis=0, keepdims=False))[None],
                (out_idx,) + (0,) * y.ndim)
            # hand the activation to the next stage
            state = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return (state, out_acc), None

        # initial carries must carry the 'varying over stage_axis' type the
        # loop body produces (shard_map VMA tracking)
        init_state = jax.lax.pcast(zero, (stage_axis,), to="varying")
        init_acc = jax.lax.pcast(jnp.zeros_like(xs_local), (stage_axis,),
                                 to="varying")
        (state, out_acc), _ = jax.lax.scan(
            tick, (init_state, init_acc), jnp.arange(T))
        # every stage except the last holds zeros; psum broadcasts the result
        return jax.lax.psum(out_acc, stage_axis)

    out = shard_map(
        stage_program, mesh=mesh,
        in_specs=(p_specs, P(None, bspec)),
        out_specs=P(None, bspec))(staged, xs)
    return out.reshape(B, *x.shape[1:])
