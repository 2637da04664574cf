"""The sort plan's grouped bucket lookup (``sortmr.grouped_lookup``).

A key in mailbox row p (its level d-1 bucket group) is compared with only
that group's B-1 child splitters.  These tests hold it to the binary search
it replaced — ``searchsorted`` over all V-1 splitters, then the division by
the level's group width — at every level, and check which path a plan's
compiled program takes.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalEngine, sort_plan
from repro.core.sortmr import GROUPED_LOOKUP_MAX, grouped_lookup


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
    bucket = jnp.clip(jnp.searchsorted(splitters, jnp.asarray(vals),
                                       side="left"), 0, V - 1)
    group = np.asarray(bucket).astype(np.int64) // width
    dest = group if compact else group * width
    return dest if valid is None else np.where(valid, dest, -1)


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
    rng = np.random.default_rng(V + levels + extra)
    B = _branching(V, levels)
    assert B - 1 <= GROUPED_LOOKUP_MAX
    x = _keys(kind, 6000, rng)
    spl = _splitters(x, V, rng)
    bucket = np.asarray(jnp.searchsorted(spl, jnp.asarray(x), side="left"))
    for d in range(levels):
        width = B ** (levels - 1 - d)
        kw = dict(B=B, width=width, parent_width=width * B, compact=shape)
        if d == 0:
            got = grouped_lookup(spl, x, None, None, **kw)
            want = _want(spl, x, None, V, width, shape)
        else:
            groups = -(-V // (width * B))
            rows = (groups if shape else V) + extra
            vals, valid = _mailbox(x, bucket, width * B, rows, shape, rng)
            ids = np.arange(rows, dtype=np.int32)
            got = grouped_lookup(spl, vals, valid, ids, **kw)
            want = _want(spl, vals, valid, V, width, shape)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"level {d}")


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
