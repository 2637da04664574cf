"""Sorting skewed and duplicated keys in one compiled program.

A value with more copies than a bucket holds is spread over every bucket
its value spans (``sortmr.grouped_lookup``), so ``sort_plan`` sorts YCSB's
Zipfian request keys, a single repeated key, two values, and keys that
each equal a splitter with ``stats.dropped == 0`` and no host retry: on the
dense route and on the kernel route (interpret mode here), at 1, 2 and 3
refinement levels.  A property test holds the lookup itself to the legal
range of each key, to the binary search for keys that tie no splitter, and
to the buckets' capacity.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalEngine, sort_plan
from repro.core.sortmr import (default_oversample, grouped_lookup,
                               quantile_splitters, splitter_runs)

from tests.test_sort_lookup import assert_lookup_contract

N, M = 1 << 13, 32                 # V = 256 buckets of capacity 96


def ycsb_zipfian(n, rng, record_count=10 ** 7):
    """YCSB's ScrambledZipfianGenerator (Zipfian constant 0.99 over 10^10
    items, ZETAN 26.46902820178302), then fnvhash64 mod record_count."""
    theta, zetan, items = 0.99, 26.46902820178302, 10 ** 10 + 1
    zeta2 = 1 + 0.5 ** theta
    eta = (1 - (2 / items) ** (1 - theta)) / (1 - zeta2 / zetan)
    u = rng.random(n)
    z = (items * (eta * u - eta + 1) ** (1 / (1 - theta))).astype(np.uint64)
    z = np.where(u * zetan < 1, 0, np.where(u * zetan < zeta2, 1, z))
    h = np.full(n, 0xCBF29CE484222325, np.uint64)
    for i in range(8):
        h ^= (z.astype(np.uint64) >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= np.uint64(1099511628211)
    return (np.abs(h.view(np.int64)) % record_count).astype(np.int32)


def skewed_keys(kind, n, rng):
    if kind == "ycsb":
        return ycsb_zipfian(n, rng)
    if kind == "all_equal":
        return np.full(n, 7, np.int32)
    if kind == "two_values":
        return rng.choice(np.asarray([-3, 11], np.int32), n, p=[0.3, 0.7])
    # every key equals a splitter: 64 values, each 4 buckets' worth of keys
    return rng.permutation(np.repeat(np.arange(64, dtype=np.int32) * 5,
                                     n // 64))


@pytest.mark.parametrize("levels", [1, 2, 3])
@pytest.mark.parametrize("route", ["dense", "kernel"])
@pytest.mark.parametrize("kind", ["ycsb", "all_equal", "two_values",
                                  "on_splitters"])
def test_sort_plan_sorts_skewed_keys(kind, route, levels):
    rng = np.random.default_rng(levels)
    x = skewed_keys(kind, N, rng)
    cap = math.ceil(3.0 * N / (N // M))
    if kind == "ycsb":              # the commonest key is several buckets
        assert np.bincount(np.unique(x, return_inverse=True)[1]).max() \
            > 3 * cap
    eng = LocalEngine(shuffle_impl=route)
    res = eng.compile(sort_plan(N, M, dtype=jnp.int32, levels=levels))(
        jnp.asarray(x), key=jax.random.PRNGKey(levels))
    assert int(res.stats.dropped) == 0
    np.testing.assert_array_equal(np.asarray(res.values), np.sort(x))
    if route == "kernel":
        assert eng.route_log.kernel == levels + 1 and eng.route_log.dense == 0


@pytest.mark.parametrize("levels,compact", [(2, True), (2, False),
                                            (3, True)])
def test_lookup_spreads_zipf_keys_within_capacity(levels, compact):
    """Route YCSB keys through every level's lookup, as the plan does: each
    destination lies in its key's legal range and equals the binary
    search's where the key ties no splitter, and the fullest final bucket
    stays within the plan's capacity."""
    n, V, slack = 1 << 15, 128, 3.0
    rng = np.random.default_rng(11)
    x = ycsb_zipfian(n, rng)
    spl, _ = quantile_splitters(jnp.asarray(x), V, default_oversample(V,
                                                                      slack),
                                jax.random.PRNGKey(5))
    first, last = splitter_runs(spl)
    B = math.ceil(V ** (1.0 / levels))
    vals, valid, ids = x, None, None
    tied = 0
    for d in range(levels):
        width = B ** (levels - 1 - d)
        got = grouped_lookup(spl, first, last, vals, valid, ids, B=B,
                             width=width, parent_width=width * B,
                             compact=compact)
        tied += assert_lookup_contract(got, spl, vals, valid, ids, V, width,
                                       B, compact)
        # the next level's mailbox: each row holds its group's keys, FIFO
        dest = np.asarray(got).reshape(-1)
        keep = dest >= 0
        dest, keys = dest[keep], np.asarray(vals).reshape(-1)[keep]
        rows = B ** (d + 1) if compact else B ** levels
        fill = np.bincount(dest, minlength=rows)
        order = np.argsort(dest, kind="stable")
        slot = np.arange(dest.size) - np.repeat(np.cumsum(fill) - fill, fill)
        vals = np.zeros((rows, fill.max()), np.int32)
        valid = np.zeros((rows, fill.max()), bool)
        vals[dest[order], slot] = keys[order]
        valid[dest[order], slot] = True
        ids = np.arange(rows, dtype=np.int32)
    assert tied > n // 10
    assert fill.max() <= math.ceil(slack * n / V)
