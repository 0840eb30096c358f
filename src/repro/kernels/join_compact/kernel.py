"""Pallas TPU kernel: compacted-stream pair expansion.

Layout: the compacted candidate stream arrives as per-entry gathers — an
(S, maxT) int32 target-slot tile stream plus (S, maxT) member/broker tables
and (S, 1) per-entry scalars (live-target count, validity, payload bytes).
The grid tiles S; each step loads a (TS, maxT) block set into VMEM and emits
the four pair grids (validity bitmap, member counts, wire bytes, broker ids)
with dense elementwise/broadcast compute only — all the gathers happened
upstream at stream assembly, so the kernel body is pure tile math.

VMEM budget per step (TS=256, maxT=64): 3 inputs + 4 outputs of
256*64*4 = 64 KB each plus 3 (TS, 1) columns -> well under 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TS = 256


def _kernel(tgt_ref, tgtn_ref, mem_ref, bid_ref, valid_ref, pay_ref,
            pv_ref, mem_out_ref, bytes_ref, bids_ref, *,
            num_brokers: int, aggregated: bool):
    tgt = tgt_ref[...]                        # (TS, maxT) int32
    tgt_n = tgtn_ref[...]                     # (TS, 1) int32
    mem = mem_ref[...]                        # (TS, maxT) int32
    bid = bid_ref[...]                        # (TS, maxT) int32
    valid = valid_ref[...]                    # (TS, 1) int32 0/1
    pay = pay_ref[...]                        # (TS, 1) int32
    ts, max_t = tgt.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (ts, max_t), 1)
    pv = (valid > 0) & (cols < tgt_n) & (tgt >= 0)
    m = jnp.where(pv, mem, 0)
    per = pay + (4 * m if aggregated else 0)  # (TS, 1) broadcasts over maxT
    pv_ref[...] = pv.astype(jnp.int8)
    mem_out_ref[...] = m
    bytes_ref[...] = jnp.where(pv, per, 0)
    bids_ref[...] = jnp.where(pv, bid, num_brokers)


@functools.partial(jax.jit, static_argnames=("num_brokers", "aggregated",
                                             "ts", "interpret"))
def join_pairs_kernel(tgt: jnp.ndarray, tgt_n: jnp.ndarray,
                      members: jnp.ndarray, brokers: jnp.ndarray,
                      valid: jnp.ndarray, payload: jnp.ndarray,
                      num_brokers: int, aggregated: bool,
                      ts: int = DEFAULT_TS, *, interpret: bool):
    """(S, maxT) gathers + (S,) scalars -> the four (S, maxT) pair grids.

    S must be a multiple of ts (ops.py pads). Returns
    (pair_valid int8, members int32, pair_bytes int32, bids int32).
    """
    s, max_t = tgt.shape
    assert s % ts == 0, (s, ts)
    grid = (s // ts,)
    col = lambda a: a.reshape(s, 1).astype(jnp.int32)
    spec2 = pl.BlockSpec((ts, max_t), lambda i: (i, 0))
    spec1 = pl.BlockSpec((ts, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, num_brokers=num_brokers,
                          aggregated=aggregated),
        grid=grid,
        in_specs=[spec2, spec1, spec2, spec2, spec1, spec1],
        out_specs=[spec2, spec2, spec2, spec2],
        out_shape=[
            jax.ShapeDtypeStruct((s, max_t), jnp.int8),
            jax.ShapeDtypeStruct((s, max_t), jnp.int32),
            jax.ShapeDtypeStruct((s, max_t), jnp.int32),
            jax.ShapeDtypeStruct((s, max_t), jnp.int32),
        ],
        interpret=interpret,
    )(tgt, col(tgt_n), members, brokers, col(valid), col(payload))
