"""The sort plan's grouped bucket lookup (``sortmr.grouped_lookup``).

A key in mailbox row p (its level d-1 bucket group) is compared with only
that group's B-1 child splitters.  These tests hold it, at every level, to
the binary search it replaced — ``searchsorted`` over all V-1 splitters,
then the division by the level's group width — for every key that equals
no splitter, and to the key's legal range for a key that does: any bucket
in [#(splitters < v), #(splitters <= v)] within its parent group.  They
also check which path a plan's compiled program takes.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalEngine, sort_plan
from repro.core.sortmr import (GROUPED_LOOKUP_MAX, grouped_lookup,
                               splitter_runs)


def _branching(V, levels):
    """The plan's branching B for V reducers (as ``sort_plan`` sets it)."""
    return max(2, math.ceil(V ** (1.0 / levels))) if V > 1 else 1


def _keys(kind, n, rng):
    if kind == "dup_int":            # max_key << n: long runs of equal keys
        return rng.integers(0, 40, n).astype(np.int32)
    if kind == "int":
        return rng.integers(-2 ** 31, 2 ** 31 - 1, n, dtype=np.int64
                            ).astype(np.int32)
    x = rng.normal(size=n).astype(np.float32)
    if kind == "float_edges":        # ±0, ±inf and NaN in keys and splitters
        x[rng.integers(0, n, n // 8)] = 0.0
        x[rng.integers(0, n, n // 8)] = -0.0
        x[rng.integers(0, n, n // 50)] = np.inf
        x[rng.integers(0, n, n // 50)] = -np.inf
        x[rng.integers(0, n, n // 50)] = np.nan
    return x


def _splitters(x, V, rng):
    """V-1 ascending splitters drawn from the keys, with runs of equals."""
    s = np.sort(x[rng.integers(0, x.size, V - 1)])
    return jnp.sort(jnp.asarray(s))      # jnp's order: NaN last, -0 == 0


def _want(splitters, vals, valid, V, width, compact):
    """The binary search's destinations: the group of bucket
    ``searchsorted(splitters, v, side="left")``."""
    bucket = jnp.clip(jnp.searchsorted(splitters, jnp.asarray(vals),
                                       side="left"), 0, V - 1)
    group = np.asarray(bucket).astype(np.int64) // width
    dest = group if compact else group * width
    return dest if valid is None else np.where(valid, dest, -1)


def _legal(splitters, vals):
    """Each key's legal buckets [#(splitters < v), #(splitters <= v)]."""
    vals = jnp.asarray(vals)
    return (np.asarray(jnp.searchsorted(splitters, vals, side="left")),
            np.asarray(jnp.searchsorted(splitters, vals, side="right")))


def _mailbox(x, bucket, parent_width, rows, compact, rng):
    """Level d's mailbox: each key in its level d-1 group's row, FIFO, then
    invalid slots that hold other keys."""
    row = bucket // parent_width
    if not compact:
        row = row * parent_width
    cap = int(np.bincount(row, minlength=rows).max()) + 3
    vals = x[rng.integers(0, x.size, (rows, cap))]
    valid = np.zeros((rows, cap), bool)
    fill = np.zeros(rows, np.int64)
    for v, r in zip(x, row):
        vals[r, fill[r]] = v
        valid[r, fill[r]] = True
        fill[r] += 1
    return vals, valid


def assert_lookup_contract(got, spl, vals, valid, ids, V, width, B,
                           compact):
    """Destinations equal the binary search's where the key ties no
    splitter, and lie in the key's legal range within its parent group
    where it does; -1 where ``valid`` is False."""
    got = np.asarray(got).astype(np.int64)
    vals = np.asarray(vals)
    lo, hi = _legal(spl, vals)
    live = np.ones(vals.shape, bool) if valid is None else valid
    tied = (hi > lo) & live
    want = _want(spl, vals, valid, V, width, compact)
    np.testing.assert_array_equal(got[~tied], want[~tied])
    pw = width * B
    if ids is not None:
        parent = np.asarray(ids, np.int64) // (1 if compact else pw)
        lo = np.maximum(lo, parent[:, None] * pw)
        hi = np.minimum(hi, parent[:, None] * pw + pw - 1)
    group = got if compact else got // width
    assert np.all(lo[tied] // width <= group[tied])
    assert np.all(group[tied] <= hi[tied] // width)
    return int(tied.sum())


# (V, levels, shape, keys, extra rows): V not a power of B, V-1 not a
# multiple of B, heavy duplicates, float edge values, and an aligned extra
# row beyond the last group (92 rows for class B's B = 91).
CASES = [
    (2048, 2, True, "dup_int", 0),
    (2048, 2, False, "dup_int", 0),
    (100, 3, True, "float_edges", 0),
    (100, 3, False, "float_edges", 0),
    (8192, 2, True, "int", 1),
    (8192, 2, False, "dup_int", 0),
    (30, 3, True, "dup_int", 2),
    (77, 1, True, "float", 0),
]


@pytest.mark.parametrize("V,levels,shape,kind,extra", CASES)
def test_grouped_lookup_equals_search_every_level(V, levels, shape, kind,
                                                  extra):
    """Bit-identical to the binary search for keys that tie no splitter,
    in the legal range for those that do.  Each level's mailbox puts every
    key in the parent of a bucket drawn from its whole legal range, so
    tied keys arrive on both sides of a parent boundary."""
    rng = np.random.default_rng(V + levels + extra)
    B = _branching(V, levels)
    assert B - 1 <= GROUPED_LOOKUP_MAX
    x = _keys(kind, 6000, rng)
    spl = _splitters(x, V, rng)
    first, last = splitter_runs(spl)
    lo, hi = _legal(spl, x)
    bucket = rng.integers(lo, hi + 1)
    tied = 0
    for d in range(levels):
        width = B ** (levels - 1 - d)
        kw = dict(B=B, width=width, parent_width=width * B, compact=shape)
        if d == 0:
            vals, valid, ids = x, None, None
        else:
            groups = -(-V // (width * B))
            rows = (groups if shape else V) + extra
            vals, valid = _mailbox(x, bucket, width * B, rows, shape, rng)
            ids = np.arange(rows, dtype=np.int32)
        got = grouped_lookup(spl, first, last, vals, valid, ids, **kw)
        assert got.dtype == jnp.int32
        tied += assert_lookup_contract(got, spl, vals, valid, ids, V, width,
                                       B, shape)
    if kind == "dup_int":
        assert tied > 0


def test_splitter_runs():
    spl = jnp.asarray([1, 3, 3, 3, 5, 7, 7], jnp.int32)
    first, last = splitter_runs(spl)
    np.testing.assert_array_equal(np.asarray(first), [0, 1, 1, 1, 4, 5, 5])
    np.testing.assert_array_equal(np.asarray(last), [0, 3, 3, 3, 4, 6, 6])
    f = jnp.asarray([-0.0, 0.0, 1.0, jnp.nan, jnp.nan], jnp.float32)
    first, last = splitter_runs(f)
    np.testing.assert_array_equal(np.asarray(first), [0, 0, 2, 3, 3])
    np.testing.assert_array_equal(np.asarray(last), [1, 1, 2, 4, 4])


def _lookup_paths(n, M, levels):
    eng = LocalEngine()
    exe = eng.compile(sort_plan(n, M, levels=levels))
    x = jnp.zeros((n,), jnp.float32)
    text = exe._fn.lower(jax.random.PRNGKey(0), x).compile().as_text()
    return [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)
            if "sort.lookup" in p.split("/")]


def test_two_level_plan_takes_the_grouped_path():
    paths = _lookup_paths(256, 16, 2)        # V = 16, B = 4
    stages = {p[p.index("mr.round") - 1] for p in paths if "mr.round" in p}
    assert stages == {"entry", "refine-1"}
    assert paths and all("sort.lookup.grouped" in p for p in paths)
    assert not any("while" in p or "jit(searchsorted)" in p for p in paths)


def test_wide_one_level_plan_keeps_the_search():
    paths = _lookup_paths(1040, 8, 1)        # V = B = 130: 129 splitters
    assert paths and all("sort.lookup.search" in p for p in paths)
    assert any("while" in p for p in paths)
