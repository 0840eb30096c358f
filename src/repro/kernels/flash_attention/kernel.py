"""Pallas TPU kernel: fused causal GQA flash attention (train / prefill).

Grid (B, H, nQ, nK); the innermost kv dimension is sequential on TPU so the
(1, 1, TQ, D) output block is revisited with running softmax state carried in
VMEM scratch (FlashAttention-2 schedule adapted to the MXU: TQ/TK tiles are
128-multiples so both matmuls hit the systolic array; fully-masked kv tiles
are skipped via pl.when on the causal diagonal).

VMEM per step (TQ=TK=256, D=128): q/k/v tiles 3*256*128*4 = 384 KB,
s/p (256,256) f32 = 256 KB, acc (256,128) f32 = 128 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TQ = 256
DEFAULT_TK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, tq: int, tk: int, n_k: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: a kv tile strictly above the diagonal contributes nothing.
    live = (ik * tk <= iq * tq + tq - 1) if causal else (ik >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)       # (TQ, D)
        k = k_ref[0, 0].astype(jnp.float32)       # (TK, D)
        v = v_ref[0, 0].astype(jnp.float32)       # (TK, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            kpos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]                        # (TQ, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (TQ, TK)
        corr = jnp.exp(m_prev - m_new)             # (TQ, 1)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows -> 0 out
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "tq", "tk", "interpret"))
def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           causal: bool = True, scale: float = 1.0,
                           tq: int = DEFAULT_TQ, tk: int = DEFAULT_TK,
                           *, interpret: bool) -> jnp.ndarray:
    b, h, s, d = q.shape
    kh = k.shape[1]
    assert h % kh == 0, (h, kh)
    g = h // kh
    assert s % tq == 0 and s % tk == 0, (s, tq, tk)
    n_q, n_k = s // tq, s // tk
    grid = (b, h, n_q, n_k)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               tq=tq, tk=tk, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
