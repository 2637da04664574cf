"""Sorting keys through ``sort_plan``: inputs, plan and outputs.

A configuration of this family gives ``total_keys`` keys in
[0, ``max_key``), drawn as NPB IS draws them (``key_distribution``
``npb_is``: floor(max_key / 4 * (r1 + r2 + r3 + r4)) of four uniform r),
and the plan's ``M`` and ``levels``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ITEM_BYTES = 4          # int32 keys


def items(cfg) -> int:
    """Input items one call answers."""
    return int(cfg["total_keys"])


def build(cfg, engine):
    """The plan, built through the program's normal entry point."""
    from repro.core import sort_plan
    return sort_plan(items(cfg), int(cfg["M"]), dtype=jnp.int32,
                     levels=int(cfg["levels"]), align=engine.aligned_nodes)


def shuffles(plan) -> int:
    """Shuffle rounds one call of ``plan`` runs."""
    return sum(st.rounds for st in plan.stages if st.shuffles)


def seed_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (any int >= 0)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_pool(cfg, seed: int, size: int, sharding=None):
    """``size`` input sets made on the device from ``seed`` in one jitted
    call: a tuple of (keys,) inputs and a tuple of the plan's PRNG keys.
    ``sharding`` places each key array (e.g. across a mesh)."""
    if cfg["key_distribution"] != "npb_is":
        raise ValueError(f"unknown key distribution "
                         f"{cfg['key_distribution']!r}")
    n, max_key = items(cfg), int(cfg["max_key"])

    def draw(key):
        ks = jax.random.split(key, 2 * size)
        keys = []
        for i in range(size):
            r = jax.random.uniform(ks[i], (4, n), jnp.float32)
            k = jnp.floor(jnp.sum(r, axis=0) * (max_key / 4))
            keys.append(jnp.minimum(k, max_key - 1).astype(jnp.int32))
        return tuple(keys), tuple(ks[size:])

    out_shardings = None
    if sharding is not None:
        out_shardings = ((sharding,) * size, None)
    keys, prng = jax.jit(draw, out_shardings=out_shardings)(seed_key(seed))
    return [(k,) for k in keys], list(prng)


def values(result):
    """The answer of one call, and the count of items it dropped."""
    return result.values, result.stats.dropped
