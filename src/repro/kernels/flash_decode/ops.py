"""Jit'd public wrappers for flash_decode: padding, normalization, dispatch."""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.flash_decode import ref
from repro.kernels.flash_decode.kernel import DEFAULT_TK, flash_decode_kernel


def decode_attention_partial(q, k, v, kv_len, scale: Optional[float] = None,
                             tk: Optional[int] = None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Kernel-backed partials; same contract as ref.decode_attention_partial."""
    b, h, d = q.shape
    s = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    tk = tk or min(DEFAULT_TK, s)
    pad = -s % tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return flash_decode_kernel(q, k, v, kv_len.astype(jnp.int32), scale=scale,
                               tk=tk, interpret=not on_tpu())


def decode_attention(q, k, v, kv_len, scale: Optional[float] = None,
                     tk: Optional[int] = None) -> jnp.ndarray:
    """Normalized decode attention (single device / single shard)."""
    acc, m, l = decode_attention_partial(q, k, v, kv_len, scale, tk)
    return ref.normalize(acc, l, q.dtype)
