"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
second level of the gradient funnel (DCI links) and the PP axis when
pipeline parallelism is enabled.

A FUNCTION, not a module constant: importing this module must not touch JAX
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=None):
    """Small mesh over however many (real or fake) local devices exist —
    used by tests and the CPU examples."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (1, n, 1), ("pod", "data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
