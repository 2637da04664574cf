"""Sorting keys with YCSB's Zipfian skew through ``sort_plan``.

A configuration of this family gives ``total_keys`` keys drawn as YCSB's
``ScrambledZipfianGenerator`` draws them (``key_distribution``
``ycsb_scrambled_zipfian``): a Zipfian draw over ``item_count`` items with
constant ``zipf_constant`` and the precomputed ``zetan`` (Gray et al.,
"Quickly generating billion-record synthetic databases"), then the 64-bit
FNV-1a hash of the draw modulo ``record_count``.  The plan, its shuffle
count and its answer are those of ``bench.plans.sort``.
"""
from __future__ import annotations

import numpy as np

from bench.plans.sort import (ITEM_BYTES, build, items, seed_key,  # noqa: F401
                              shuffles, values)

#: 64-bit FNV-1a, as YCSB's ``Utils.fnvhash64``
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``fnvhash64`` of each value: FNV-1a over its 8 bytes, low
    byte first, and the absolute value of the signed result."""
    v = np.asarray(v, np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for i in range(8):
        h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64))


def zipfian(u: np.ndarray, items_n: int, theta: float,
            zetan: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextValue`` for uniform draws ``u`` in
    [0, 1) over ``items_n`` items (its base is 0)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items_n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items_n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def ycsb_keys(cfg, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` request keys of the configuration's scrambled Zipfian."""
    # YCSB builds its ZipfianGenerator over [0, ITEM_COUNT]: ITEM_COUNT + 1
    # items.
    z = zipfian(rng.random(n), int(cfg["item_count"]) + 1,
                float(cfg["zipf_constant"]), float(cfg["zetan"]))
    return (fnvhash64(z) % int(cfg["record_count"])).astype(np.int32)


def make_pool(cfg, seed: int, size: int, sharding=None):
    """``size`` input sets drawn on the host from ``seed`` (numpy's PCG64)
    and placed on the device: a list of (keys,) inputs and a list of the
    plan's PRNG keys.  ``sharding`` places each key array."""
    import jax
    if cfg["key_distribution"] != "ycsb_scrambled_zipfian":
        raise ValueError(f"unknown key distribution "
                         f"{cfg['key_distribution']!r}")
    rng = np.random.default_rng(seed)
    n = items(cfg)
    keys = [jax.device_put(ycsb_keys(cfg, n, rng), sharding)
            for _ in range(size)]
    prng = jax.random.split(seed_key(seed), size)
    return [(k,) for k in keys], list(prng)
