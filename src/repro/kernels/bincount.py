"""Bucket-histogram Pallas kernels — the fan-in counting round of the shuffle.

Every shuffle/dispatch round of the paper starts by counting how many items
target each reducer (Thm 4.2's R1 "send the counts" round; MoE dispatch's
tokens-per-expert).  On TPU a histogram is phrased as a one-hot
reduction: each VMEM chunk of ids becomes a (chunk, n_buckets) comparison
matrix reduced over rows; the sequential grid accumulates tile partials in
a VMEM carry — a depth-1 funnel in VMEM.

:func:`bincount_tiles` is the multi-tile radix front end of
:func:`repro.core.kshuffle.kernel_shuffle`: one launch emits, per input
tile, the tile's own counts, the *cross-tile exclusive prefix* of counts
(how many same-bucket items earlier tiles hold — the paper's "send the
counts" table, folded into the sequential grid's carry), and the *in-tile
bucket offsets* (exclusive prefix along the bucket axis).  :func:`bincount`,
the one global histogram, is its last carry.

Mosaic layout: a grid step takes ``_ROWS`` = 8 tiles (one sublane group),
the bucket axis is padded to whole 128-lane vregs, and the tile is counted
in lane chunks whose transposed ids compare against a lane iota of buckets,
so the (chunk, buckets) one-hot slab — not (tile, buckets) — bounds VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefix_scan import lane_cumsum

#: tiles per grid step: one sublane group of the (8, 128) int32 vreg
_ROWS = 8
_LANES = 128
#: most buckets one launch counts: the v5e compiler accepts 2^15 within its
#: default 16 MiB scoped VMEM and refuses 2^16 (the double-buffered
#: (8, buckets) output blocks and one-hot slab overflow it)
MAX_BUCKETS = 1 << 15
#: elements of one (chunk, buckets) one-hot slab: the chunk is the widest
#: power-of-two lane count within it, and at least one 128-lane group
_ONEHOT_BUDGET = 1 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bincount_tiles_kernel(ids_ref, c_ref, p_ref, f_ref, carry_ref, *,
                           chunk: int):
    """Grid step t counts tiles 8t..8t+7 and snapshots the running
    cross-tile totals.

    TPU grids execute sequentially, so ``carry`` holds the bucket totals of
    all tiles to the *left* — written out before each tile's counts join it,
    giving the exclusive cross-tile prefix each tile's items rank after.
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    n_buckets = c_ref.shape[1]
    buckets = jax.lax.broadcasted_iota(jnp.int32, (chunk, n_buckets), 1)
    c_ref[...] = jnp.zeros_like(c_ref)

    def count_chunk(i, _):
        start = pl.multiple_of(i * chunk, chunk)
        ids_t = ids_ref[:, pl.ds(start, chunk)].T        # (chunk, _ROWS)
        for r in range(_ROWS):
            onehot = (ids_t[:, r:r + 1] == buckets).astype(jnp.int32)
            c_ref[r:r + 1, :] += jnp.sum(onehot, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, ids_ref.shape[1] // chunk, count_chunk, 0)
    counts = c_ref[...]
    carry = carry_ref[...]
    for r in range(_ROWS):
        p_ref[r:r + 1, :] = carry                     # items in earlier tiles
        carry = carry + counts[r:r + 1, :]
    carry_ref[...] = carry
    f_ref[...] = lane_cumsum(counts) - counts         # in-tile bucket offsets


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def bincount_tiles(tiles: jnp.ndarray, n_buckets: int, *,
                   interpret: bool = False):
    """Per-tile histogram + fused cross-tile/in-tile exclusive scans.

    tiles: (T, tile_n) int32 ids in [0, n_buckets); ids < 0 are ignored.
    Returns three (T, n_buckets) int32 arrays:

    - ``counts[t, b]``  — occurrences of b in tile t;
    - ``tile_prefix[t, b]`` — occurrences of b in tiles 0..t-1 (exclusive
      cross-tile scan: the global rank offset of tile t's first b-item);
    - ``bucket_offsets[t, b]`` — occurrences of buckets 0..b-1 in tile t
      (exclusive in-tile scan: the first slot of b's run in a bucket-sorted
      tile).

    Bucket totals over all tiles are ``tile_prefix[-1] + counts[-1]``.
    Tiles, tile width and buckets are padded to the (8, 128) vreg tiling
    with ignored ids and empty buckets; at most ``MAX_BUCKETS`` buckets.
    """
    if tiles.ndim != 2:
        raise ValueError("bincount_tiles expects (T, tile_n)")
    T, tile_n = tiles.shape
    if T == 0 or tile_n == 0:
        z = jnp.zeros((T, n_buckets), jnp.int32)
        return z, z, z
    if n_buckets > MAX_BUCKETS:
        raise ValueError(
            f"bincount_tiles: n_buckets={n_buckets} exceeds the one-hot "
            f"VMEM budget ({MAX_BUCKETS} buckets)")
    v_pad = _round_up(n_buckets, _LANES)
    chunk = _LANES
    while chunk * 2 * v_pad <= _ONEHOT_BUDGET and chunk * 2 <= tile_n:
        chunk *= 2
    t_pad, n_pad = _round_up(T, _ROWS), _round_up(tile_n, chunk)
    tiles = jnp.pad(tiles, ((0, t_pad - T), (0, n_pad - tile_n)),
                    constant_values=-1)
    out_shape = jax.ShapeDtypeStruct((t_pad, v_pad), jnp.int32)
    spec = pl.BlockSpec((_ROWS, v_pad), lambda i: (i, 0))
    counts, prefix, offsets = pl.pallas_call(
        functools.partial(_bincount_tiles_kernel, chunk=chunk),
        grid=(t_pad // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, n_pad), lambda i: (i, 0))],
        out_specs=[spec, spec, spec],
        out_shape=[out_shape, out_shape, out_shape],
        scratch_shapes=[pltpu.VMEM((1, v_pad), jnp.int32)],
        interpret=interpret,
    )(tiles)
    return (counts[:T, :n_buckets], prefix[:T, :n_buckets],
            offsets[:T, :n_buckets])


@functools.partial(jax.jit, static_argnames=("n_buckets", "block_t",
                                             "interpret"))
def bincount(ids: jnp.ndarray, n_buckets: int, *, block_t: int = 1024,
             interpret: bool = False) -> jnp.ndarray:
    """Count occurrences of each id in [0, n_buckets); ids < 0 are ignored.

    ids: (n,) int32, counted as tiles of ``block_t`` by
    :func:`bincount_tiles`.  Returns (n_buckets,) int32.
    """
    if ids.ndim != 1:
        raise ValueError("bincount expects (n,)")
    n = ids.shape[0]
    if n == 0:                       # empty input: nothing to count
        return jnp.zeros((n_buckets,), jnp.int32)
    block_t = min(block_t, n)
    ids = jnp.pad(ids, (0, _round_up(n, block_t) - n), constant_values=-1)
    counts, prefix, _ = bincount_tiles(ids.reshape(-1, block_t), n_buckets,
                                       interpret=interpret)
    return prefix[-1] + counts[-1]
