"""GPipe-style pipeline parallelism over a mesh axis via shard_map.

The `pod` axis can carry pipeline stages instead of data parallelism: each
stage owns a contiguous block of superlayers; microbatches stream through
with ``jax.lax.ppermute`` moving activations stage-to-stage. The schedule is
the classic GPipe fill-drain loop (num_microbatches + num_stages - 1 ticks);
bubble fraction = (S-1)/(M+S-1).

This module implements the *forward* pipeline (serving / evaluation) and a
loss pipeline whose backward is derived by jax.grad through the ppermute
(reverse collective permute) — the standard JAX treatment.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P



def pipeline_forward(mesh: Mesh, axis: str, stage_fn: Callable,
                     num_microbatches: int):
    """Build a pipelined forward over ``axis``.

    stage_fn(stage_params, x) -> x, applied by every stage to whatever
    microbatch currently resides on it. Inputs enter at stage 0, outputs
    leave from the last stage.

    Returns fn(stage_params_stacked, x_microbatched) where
      stage_params_stacked: leaves (S, ...) sharded over `axis`,
      x_microbatched: (M, B_micro, ...) replicated over `axis`.
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params, xs):
        m = xs.shape[0]
        ticks = m + n_stages - 1
        stage = jax.lax.axis_index(axis)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            state, outputs = carry
            # Stage 0 injects microbatch t (if any remain).
            inject = jnp.where(t < m, t, m - 1)
            x_in = xs[inject]
            state = jnp.where(stage == 0, x_in, state)
            live = (t - stage >= 0) & (t - stage < m)
            y = stage_fn(stage_params, state)
            y = jnp.where(live, y, state)
            # Last stage emits microbatch t - (S-1).
            emit_idx = t - (n_stages - 1)
            is_emit = (stage == n_stages - 1) & (emit_idx >= 0)
            slot = jnp.maximum(emit_idx, 0)
            outputs = outputs.at[slot].set(
                jnp.where(is_emit, y, outputs[slot]))
            # Shift activations downstream.
            state = jax.lax.ppermute(y, axis, fwd_perm)
            return (state, outputs), ()

        # carriers must be device-varying from the start (shard_map vma rules)
        state0 = jax.lax.pcast(jnp.zeros_like(xs[0]), axis, to="varying")
        outputs0 = jax.lax.pcast(jnp.zeros_like(xs), axis, to="varying")
        (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0),
                                       jnp.arange(ticks))
        # Outputs live on the last stage; broadcast to all for the caller.
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis)
        return outputs

    def run(stage_params_stacked, x_microbatched):
        p_specs = jax.tree.map(lambda _: P(axis), stage_params_stacked)
        fn = jax.shard_map(
            lambda sp, xx: pipelined(
                jax.tree.map(lambda a: a[0], sp), xx),
            mesh=mesh,
            in_specs=(p_specs, P()),
            out_specs=P())
        return fn(stage_params_stacked, x_microbatched)

    return run


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
