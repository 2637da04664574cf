"""In-VMEM bitonic key-value sort Pallas kernel — the reducer-local sort.

The paper's sample sort (§4.3) bottoms out when a bucket fits one reducer
(<= M items); that reducer then sorts locally.  On TPU "one reducer" is one
VMEM tile, and the TPU-native local sort is a bitonic network: data-oblivious
compare-exchange stages — no gathers, no divergence, fully VPU-vectorized.
n must be a power of two (pad with +inf).

Stages: for k in 2,4,..,n (merge size), for j in k/2,..,1 (distance):
elements at distance j swap so each k-block becomes ascending/descending by
position — log^2(n) dense passes over the tile.  Each pass fetches every
element's partner (index i XOR j) with two lane rolls, by +j and -j, and a
lane-iota mask, so rows stay 2-D (Mosaic refuses the 4-D pair reshape); the
stage loop is a ``fori_loop`` over dynamic roll distances, which keeps the
compiled kernel one stage long.

Rows sort independently, so the launch *grids over row blocks*: each grid
step sorts ``block_rows`` rows (a multiple of 8 sublanes) in one VMEM tile
of <= _ROW_BLOCK_ELEMS elements, rows padded to whole 128-lane vregs.  A
(T, tile_n) call — the multi-tile radix shuffle's T local sorts
(repro.core.kshuffle) — is therefore ONE pallas_call at any T; only a
single 8-row block's padded width is bounded by VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8
_LANES = 128


def _bitonic_kernel(k_ref, v_ref, ok_ref, ov_ref):
    keys, vals = k_ref[...], v_ref[...]
    n = keys.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)

    def merge(log_k, kv):
        k = jnp.left_shift(jnp.int32(1), log_k)
        ascending = (lane & k) == 0        # floor(index / k) is even

        def compare_exchange(step, kv):
            keys, vals = kv
            j = jnp.right_shift(k, step + 1)
            upper = (lane & j) != 0        # partner is index - j, else + j

            def partner(x):
                return jnp.where(upper, pltpu.roll(x, j, 1),
                                 pltpu.roll(x, n - j, 1))

            pk, pv = partner(keys), partner(vals)
            lo = jnp.where(upper, pk, keys)
            hi = jnp.where(upper, keys, pk)
            swap = (ascending & (lo > hi)) | (~ascending & (lo < hi))
            return jnp.where(swap, pk, keys), jnp.where(swap, pv, vals)

        return jax.lax.fori_loop(0, log_k, compare_exchange, kv)

    keys, vals = jax.lax.fori_loop(1, n.bit_length(), merge, (keys, vals))
    ok_ref[...] = keys
    ov_ref[...] = vals


#: per-grid-step VMEM budget (elements per array) — one row block
_ROW_BLOCK_ELEMS = 1 << 16


#: widest row whose 8-row block fits ``_ROW_BLOCK_ELEMS``
MAX_ROW_WIDTH = _ROW_BLOCK_ELEMS // _SUBLANES


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(keys: jnp.ndarray, values: jnp.ndarray, *,
                 interpret: bool = False):
    """Sort each row of (rows, n) ascending by key, permuting values along.

    n is padded to the next power of two, and to at least one 128-lane
    vreg, with max-value keys (dropped on return).  Rows are independent
    networks, so the launch grids over blocks of a multiple of 8 rows within
    ``_ROW_BLOCK_ELEMS`` — any row count fits; only 8 rows of the padded
    width must fit one VMEM tile (8 * n_pad <= _ROW_BLOCK_ELEMS).
    """
    if keys.shape != values.shape or keys.ndim != 2:
        raise ValueError("bitonic_sort expects matching (rows, n) arrays")
    rows, n = keys.shape
    if n == 0 or rows == 0:          # empty rows are trivially sorted
        return keys, values
    n_pad = _LANES
    while n_pad < n:
        n_pad *= 2
    if n_pad > MAX_ROW_WIDTH:
        raise ValueError(
            f"bitonic_sort: one row of n={n} (padded {n_pad}) exceeds the "
            f"single-VMEM-tile budget ({_ROW_BLOCK_ELEMS} elements per "
            f"{_SUBLANES}-row block); split the row into tiles first (see "
            f"repro.core.kshuffle)")
    big = (jnp.finfo(keys.dtype).max
           if jnp.issubdtype(keys.dtype, jnp.floating)
           else jnp.iinfo(keys.dtype).max)
    rows_8 = -(-rows // _SUBLANES) * _SUBLANES
    block_rows = min(rows_8, _ROW_BLOCK_ELEMS // n_pad
                     // _SUBLANES * _SUBLANES)
    grid_r = -(-rows // block_rows)
    # Padded rows sort (harmlessly) in-block; padded lanes sort last.
    pad = ((0, grid_r * block_rows - rows), (0, n_pad - n))
    keys = jnp.pad(keys, pad, constant_values=big)
    values = jnp.pad(values, pad)
    spec = pl.BlockSpec((block_rows, n_pad), lambda i: (i, 0))
    out_k, out_v = pl.pallas_call(
        _bitonic_kernel,
        grid=(grid_r,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(keys.shape, keys.dtype),
                   jax.ShapeDtypeStruct(values.shape, values.dtype)],
        interpret=interpret,
    )(keys, values)
    return out_k[:rows, :n], out_v[:rows, :n]
