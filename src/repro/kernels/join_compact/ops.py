"""Jit'd public wrapper for the join_compact kernel.

Handles: S-padding to the tile size, dtype canonicalization, int8->bool
conversion, and backend dispatch (Pallas compiled on TPU, interpret mode
elsewhere). Drop-in for ``ref.join_pairs`` — the ``join_fn`` hook of
``core/plans.py join_param_stream``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.join_compact.kernel import DEFAULT_TS, join_pairs_kernel


def join_pairs(tgt: jnp.ndarray, tgt_n: jnp.ndarray, members: jnp.ndarray,
               brokers: jnp.ndarray, valid: jnp.ndarray,
               payload: jnp.ndarray, num_brokers: int, aggregated: bool,
               ts: int = DEFAULT_TS):
    """Same contract as ``ref.join_pairs`` (bit-identical: all-integer)."""
    s = tgt.shape[0]
    s_pad = -s % ts
    if s_pad:
        pad2 = ((0, s_pad), (0, 0))
        tgt = jnp.pad(tgt, pad2, constant_values=-1)
        members = jnp.pad(members, pad2)
        brokers = jnp.pad(brokers, pad2)
        tgt_n = jnp.pad(tgt_n, (0, s_pad))
        valid = jnp.pad(valid, (0, s_pad))
        payload = jnp.pad(payload, (0, s_pad))
    i32 = lambda a: a.astype(jnp.int32)
    pv, mem, by, bids = join_pairs_kernel(
        i32(tgt), i32(tgt_n), i32(members), i32(brokers), i32(valid),
        i32(payload), num_brokers, aggregated, ts=ts,
        interpret=not on_tpu())
    return pv[:s].astype(jnp.bool_), mem[:s], by[:s], bids[:s]
