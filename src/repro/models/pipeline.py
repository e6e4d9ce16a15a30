"""Pipeline parallelism (GPipe) over a mesh axis via shard_map + ppermute.

The layer stack is split into S contiguous stages, stage s owned by mesh
slice s of the pipeline axis (layer-stacked params sharded P(axis) on dim 0).
Microbatches stream through: at tick t, stage s runs microbatch t-s; between
ticks, activations move one hop with `ppermute` (whose transpose is the
reverse permute, so `jax.grad` differentiates straight through the schedule —
the backward pipeline emerges from autodiff).

This is the cross-pod option for multi-pod training: inter-pod traffic
becomes (mb, S, d) activations once per tick instead of gradient all-reduces
of the full parameter set.  Bubble fraction = (S-1)/(n_micro + S - 1).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_params, x_micro, stage_fn: Callable, *, mesh,
                   axis: str = "pod", inner_specs=P()):
    """Run the pipeline.

    stage_params: pytree, leaves (S*per_stage, ...) sharded P(axis) on dim 0
                  (each stage holds `per_stage` layers).
    x_micro:      (n_micro, mb, seq, d) — microbatched activations (replicated
                  along `axis`; shard other dims via `inner_specs`).
    stage_fn(local_params, x) -> y: applies ONE stage's layers.

    Returns (n_micro, mb, seq, d) outputs (as produced by the last stage,
    valid on every device after the closing gather).
    """
    s_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + s_stages - 1

    def body(params_local, xs):
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)          # in-flight activation
        out = jnp.zeros_like(xs)                       # last stage's results

        def tick(t, carry):
            state, out = carry
            # stage 0 ingests microbatch t (if any); others use state
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, feed, state)
            y = stage_fn(params_local, x_in)
            # the last stage writes microbatch t-(S-1) to the output buffer
            out_slot = jnp.clip(t - (s_stages - 1), 0, n_micro - 1)
            take = (stage == s_stages - 1) & (t >= s_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out, out_slot, 0,
                                               keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(take, y, cur), out_slot, 0)
            # move activations one hop forward (ring; last->first is ignored)
            state = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s_stages) for i in range(s_stages)])
            return state, out

        _, out = jax.lax.fori_loop(0, ticks, tick, (state, out))
        # broadcast the last stage's buffer to every stage (psum of one-hot)
        mask = (stage == s_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    in_leaf_spec = jax.tree.map(lambda _: P(axis), stage_params)
    # Fully manual over every mesh axis: partial-manual (auto=) lowering of
    # this schedule trips XLA's PartitionId/manual-subgroup limitations on the
    # pinned jax version, so non-pipeline axes are handled by `inner_specs`
    # instead (shard the microbatch dim there; unmentioned axes replicate).
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(in_leaf_spec, inner_specs),
        out_specs=inner_specs, check_vma=False,
    )(stage_params, x_micro)


__all__ = ["pipeline_apply"]
