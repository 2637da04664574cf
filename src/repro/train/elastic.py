"""Elastic scaling: resume a run on a different topology.

Checkpoints are topology-agnostic (unsharded logical tensors), so elasticity
reduces to (a) choosing a mesh for the devices that are currently healthy,
and (b) resharding the restored tree onto it.  ``plan_mesh`` picks the
largest (pod, data, model) factorization our sharding rules support from an
arbitrary healthy-device count; ``reshard_tree`` re-places a restored tree.

On a real cluster the coordinator detects node loss (jax.distributed
heartbeats), the job restarts with the survivors, and this module maps the
old run onto the new mesh.  The simulated-failure test exercises exactly
that path on fake devices.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from ..models import sharding as shmod


def plan_mesh(n_devices: Optional[int] = None,
              model_parallel: int = 16) -> Mesh:
    """Largest usable (pod, data, model) mesh from the healthy devices.

    Keeps the TP degree fixed (kernel-friendly), gives the remainder to the
    data axis, and drops stragglers that don't factorize (e.g. 511 healthy
    devices -> 1x31x16 mesh, 15 spares idle)."""
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if n > len(devs):
        # A "resume on 512" request must not quietly resume on 8: slicing
        # devs[:dp*mp] below would silently clamp to the healthy count.
        raise ValueError(
            f"plan_mesh: requested n_devices={n} but only {len(devs)} "
            f"devices are healthy — pass n_devices<={len(devs)} (or None "
            f"to use all healthy devices)")
    if n < 1:
        raise ValueError(f"plan_mesh: n_devices must be >= 1, got {n}")
    mp = min(model_parallel, n)
    while n % mp and mp > 1:
        mp -= 1
    dp = n // mp
    return jax.make_mesh((dp, mp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devs[:dp * mp])


def reshard_tree(tree: Any, mesh: Mesh) -> Any:
    """Re-place a (restored, host-resident) tree onto ``mesh`` according to
    the standard parameter rules."""
    with shmod.use_mesh(mesh):
        specs = shmod.tree_param_specs(tree)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)
