"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state. Single pod: (16, 16) = 256 v5e chips, axes (data, model). Multi-pod:
(2, 16, 16) = 512 chips, axes (pod, data, model); `pod` composes with `data`
for batch sharding (DP across pods) or carries pipeline stages in PP mode.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto-typed."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Smoke-scale mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
