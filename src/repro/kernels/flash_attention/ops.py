"""Jit'd public wrapper for flash_attention: padding, scale, dispatch."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.flash_attention.kernel import (DEFAULT_TK, DEFAULT_TQ,
                                                  flash_attention_kernel)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: Optional[float] = None,
                    tq: Optional[int] = None, tk: Optional[int] = None) -> jnp.ndarray:
    """q (B, H, S, D), k/v (B, KH, S, D) -> (B, H, S, D).

    Pads S to the tile size (padded kv is masked out by causality for the
    padded q rows; for non-causal use, padded kv keys are masked via a huge
    negative bias on padded rows — handled by padding k with zeros and
    relying on causal=True for trainining paths; non-causal callers must pass
    tile-aligned S).
    """
    b, h, s, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    tq = tq or min(DEFAULT_TQ, s)
    tk = tk or min(DEFAULT_TK, s)
    pad = -s % max(tq, tk)
    if pad:
        if not causal:
            raise ValueError("non-causal flash_attention requires tile-aligned S")
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = flash_attention_kernel(q, k, v, causal=causal, scale=scale,
                                 tq=tq, tk=tk, interpret=not on_tpu())
    return out[:, :, :s]
