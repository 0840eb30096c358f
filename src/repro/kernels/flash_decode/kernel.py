"""Pallas TPU kernel: split-KV decode attention (FlashDecoding on TPU).

One query token per sequence against a long KV cache. Grid (B, nK) — the kv
dimension is innermost/sequential, all heads are processed per step (decode is
memory-bound: each KV byte is read exactly once; the (H, TK) logit tile is
tiny). Emits *unnormalized* partials (acc, m, l) so the sequence-parallel
serving path (shard_map over the kv axis) can merge shards with one small
collective instead of re-reading the cache.

VMEM per step (H=32, KH=8, TK=512, D=128): k/v tiles 2*8*512*128*4 = 4 MB,
logits 32*512*4 = 64 KB, acc 32*128*4 = 16 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TK = 512
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
            m_scr, l_scr, acc_scr, *, scale: float, tk: int, n_k: int,
            kh: int, g: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (H, D)
    k = k_ref[0].astype(jnp.float32)              # (KH, TK, D)
    v = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]
    qg = q.reshape(kh, g, d)
    s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale  # (KH, G, TK)
    kv_len = len_ref[0]
    kpos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (kh, g, tk), 2)
    s = jnp.where(kpos < kv_len, s, NEG_INF)
    h = kh * g
    s = s.reshape(h, tk)
    m_prev = m_scr[...]                            # (H, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(s <= NEG_INF, 0.0, p)            # dead slots contribute 0
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.reshape(kh, g, tk), v,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)  # (KH, G, D)
    acc_scr[...] = acc_scr[...] * corr + pv.reshape(h, d)
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = jnp.where(m_scr[...] <= NEG_INF, -jnp.inf, m_scr[...])[:, 0]
        l_ref[0] = l_scr[...][:, 0]


@functools.partial(jax.jit, static_argnames=("scale", "tk", "interpret"))
def flash_decode_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        kv_len: jnp.ndarray, scale: float,
                        tk: int = DEFAULT_TK, *, interpret: bool):
    """q (B, H, D), k/v (B, KH, S, D), kv_len (B,) int32.

    Returns (acc (B, H, D) f32, m (B, H) f32, l (B, H) f32) — unnormalized.
    """
    b, h, d = q.shape
    kh, s = k.shape[1], k.shape[2]
    assert h % kh == 0 and s % tk == 0, (h, kh, s, tk)
    g = h // kh
    n_k = s // tk
    kernel = functools.partial(_kernel, scale=scale, tk=tk, n_k=n_k, kh=kh, g=g)
    return pl.pallas_call(
        kernel,
        grid=(b, n_k),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, ik: (b_,)),
            pl.BlockSpec((1, h, d), lambda b_, ik: (b_, 0, 0)),
            pl.BlockSpec((1, kh, tk, d), lambda b_, ik: (b_, 0, ik, 0)),
            pl.BlockSpec((1, kh, tk, d), lambda b_, ik: (b_, 0, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, d), lambda b_, ik: (b_, 0, 0)),
            pl.BlockSpec((1, h), lambda b_, ik: (b_, 0)),
            pl.BlockSpec((1, h), lambda b_, ik: (b_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, q, k, v)
