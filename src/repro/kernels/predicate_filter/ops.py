"""Jit'd public wrapper for the predicate_filter kernel.

Handles: conditionsList canonicalization (cached per table), N-padding to the
tile size, int8->bool conversion, and backend dispatch (Pallas compiled on
TPU, interpret mode elsewhere).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.predicates import CompiledConditions
from repro.kernels import on_tpu
from repro.kernels.predicate_filter import ref
from repro.kernels.predicate_filter.kernel import DEFAULT_TN, predicate_filter_kernel


_CANON_CACHE: Dict[Tuple, Tuple] = {}


def canonical_arrays(conds: CompiledConditions, num_fields: int):
    """Cached interval canonicalization. Values are HOST numpy arrays so the
    cache is trace-safe: ``predicate_filter`` is called inside the engine's
    jitted plans, and caching device arrays created under a trace would leak
    tracers into later traces. numpy operands become per-trace constants at
    the jit boundary."""
    key = (conds.field_idx.tobytes(), conds.op.tobytes(), conds.value.tobytes(),
           conds.npreds.tobytes(), conds.field_idx.shape, num_fields)
    if key not in _CANON_CACHE:
        ic = ref.canonicalize(conds, num_fields)
        _CANON_CACHE[key] = (ic.lo, ic.hi, ic.neq)
    return _CANON_CACHE[key]


def predicate_filter(fields: jnp.ndarray, conds: CompiledConditions,
                     tn: int = DEFAULT_TN) -> jnp.ndarray:
    """(N, F) int32 records x conditionsList -> (N, C) bool match bitmap."""
    lo, hi, neq = canonical_arrays(conds, int(fields.shape[1]))
    return predicate_filter_padded(fields, lo, hi, neq, tn=tn,
                                   interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def predicate_filter_padded(fields: jnp.ndarray, lo: jnp.ndarray,
                            hi: jnp.ndarray, neq: jnp.ndarray,
                            tn: int = DEFAULT_TN,
                            *, interpret: bool) -> jnp.ndarray:
    n = fields.shape[0]
    n_pad = -n % tn
    if n_pad:
        fields = jnp.pad(fields, ((0, n_pad), (0, 0)))
    out = predicate_filter_kernel(fields, lo, hi, neq, tn=tn, interpret=interpret)
    return out[:n].astype(jnp.bool_)


def predicate_filter_rows(fields: jnp.ndarray, conds: CompiledConditions,
                          tn: int = DEFAULT_TN) -> jnp.ndarray:
    """(C, N, F) stacked row blocks -> (C, N) bool: channel c's conjunction
    evaluated on its own block only.

    This is the fused executor's window / candidate-recheck shape, where each
    channel gathers a different row window. The kernel runs with a single-row
    bounds table per channel and is batched by vmap — pallas_call lowers the
    channel axis onto a leading grid dimension, one device call total.
    """
    lo, hi, neq = canonical_arrays(conds, int(fields.shape[-1]))
    interpret = not on_tpu()

    def one(f, l, h, q):
        return predicate_filter_padded(f, l[None], h[None], q[None], tn=tn,
                                       interpret=interpret)[:, 0]

    return jax.vmap(one)(fields, lo, hi, neq)


def predicate_filter_ref(fields: jnp.ndarray, conds: CompiledConditions) -> jnp.ndarray:
    """Oracle path with identical canonicalization (for allclose tests)."""
    lo, hi, neq = canonical_arrays(conds, int(fields.shape[1]))
    return ref.predicate_filter(fields, lo, hi, neq)
