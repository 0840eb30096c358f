"""Jit'd public wrapper for spatial_match: padding + backend dispatch.

Padding uses +inf sentinel coordinates so padded rows/cols never match.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.spatial_match.kernel import (DEFAULT_TR, DEFAULT_TU,
                                                spatial_match_kernel)

# Far sentinel for padded rows/users: coordinates so distant that dist^2
# overflows float32 to +inf, which is never < radius^2. The engine's stacked
# user sets reuse the same value for their shape-bucket padding.
FAR = 1e30
_FAR = FAR


def spatial_match(tweet_locs: jnp.ndarray, user_locs: jnp.ndarray,
                  radius) -> jnp.ndarray:
    """(R, 2) x (U, 2) -> (R, U) bool; drop-in for ref.spatial_match.

    Also accepts stacked (C, R, 2) x (C, U, 2) inputs with per-channel radii
    (C,), vmapping the kernel over the channel axis (the fused executor's
    layout — pallas_call lowers the batch onto a leading grid dimension).
    """
    if tweet_locs.ndim == 3:
        radii = jnp.broadcast_to(jnp.asarray(radius, jnp.float32),
                                 (tweet_locs.shape[0],))
        return jax.vmap(spatial_match)(tweet_locs, user_locs, radii)
    return _padded(tweet_locs, user_locs,
                   jnp.asarray(radius, jnp.float32) ** 2,
                   interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("tr", "tu", "interpret"))
def _padded(tweet_locs, user_locs, radius2, tr: int = DEFAULT_TR,
            tu: int = DEFAULT_TU, *, interpret: bool):
    r, u = tweet_locs.shape[0], user_locs.shape[0]
    rp, up = -r % tr, -u % tu
    if rp:
        tweet_locs = jnp.pad(tweet_locs, ((0, rp), (0, 0)), constant_values=_FAR)
    if up:
        user_locs = jnp.pad(user_locs, ((0, up), (0, 0)), constant_values=-_FAR)
    out = spatial_match_kernel(tweet_locs, user_locs.T, radius2, tr=tr,
                               tu=tu, interpret=interpret)
    return out[:r, :u].astype(jnp.bool_)
