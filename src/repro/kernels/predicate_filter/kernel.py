"""Pallas TPU kernel: ingestion-time conditionsList evaluation (paper Alg. 2).

Layout: records arrive as an (N, F) int32 tile stream; conditions are a dense
(C, F) interval table resident in VMEM (C = channels, F = fields; both small —
the table is a few KB). The grid tiles N; each step loads a (TN, F) record
block into VMEM, broadcasts it against the (C, F) bounds and reduces over F,
emitting a (TN, C) int8 match bitmap.

VMEM budget per step (TN=256, F=16, C=128):
  records 256*16*4 = 16 KB; bounds 3*128*16*4 = 24 KB;
  broadcast compare (TN, C, F) int8 ≈ 512 KB; out 32 KB  -> well under 16 MB.
The F-reduction is unrolled (F is static) so the working set stays (TN, C).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.predicate_filter.ref import NEQ_NONE

DEFAULT_TN = 256


def _kernel(fields_ref, lo_ref, hi_ref, neq_ref, out_ref):
    x = fields_ref[...]                       # (TN, F) int32
    lo = lo_ref[...]                          # (C, F)
    hi = hi_ref[...]
    neq = neq_ref[...]
    tn = x.shape[0]
    c = lo.shape[0]
    acc = jnp.ones((tn, c), dtype=jnp.bool_)
    # F is static and small: unrolled per-field compare keeps the live set 2-D.
    for f in range(x.shape[1]):
        xf = x[:, f][:, None]                 # (TN, 1)
        ok = (xf >= lo[:, f][None, :]) & (xf <= hi[:, f][None, :])
        ok &= (xf != neq[:, f][None, :]) | (neq[:, f] == NEQ_NONE)[None, :]
        acc = acc & ok
    out_ref[...] = acc.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def predicate_filter_kernel(fields: jnp.ndarray, lo: jnp.ndarray,
                            hi: jnp.ndarray, neq: jnp.ndarray,
                            tn: int = DEFAULT_TN,
                            *, interpret: bool) -> jnp.ndarray:
    """fields (N, F) int32, bounds (C, F) int32 -> (N, C) int8 bitmap.

    N must be a multiple of tn (ops.py pads).
    """
    n, f = fields.shape
    c = lo.shape[0]
    assert n % tn == 0, (n, tn)
    grid = (n // tn,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, f), lambda i: (i, 0)),
            pl.BlockSpec((c, f), lambda i: (0, 0)),
            pl.BlockSpec((c, f), lambda i: (0, 0)),
            pl.BlockSpec((c, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.int8),
        interpret=interpret,
    )(fields, lo, hi, neq)
