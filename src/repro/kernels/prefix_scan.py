"""Blocked prefix-sum Pallas kernel — Lemma 2.2's d-ary tree folded into VMEM.

The paper's tree computes all-prefix-sums in two phases (bottom-up partial
sums, top-down offset distribution).  On TPU the same structure becomes a
*blocked* scan: the sequence is tiled into VMEM blocks; within a block the
VPU computes a local cumulative sum (the subtree) by log-step lane rolls
(:func:`lane_cumsum`, shared with the bincount kernel), and a carry —
the running "sum of everything to the left", i.e. the paper's s_{p(v)} —
flows sequentially across grid steps (TPU grids execute in order, so the
carry lives in a VMEM scratch accumulator).

Used for MoE dispatch offsets (tokens-per-expert -> send offsets) and as the
building block of the chunked SSM scan.  The kernel shuffle's cross-tile
count scan used to be a call here too; it now lives fused inside
:func:`repro.kernels.bincount.bincount_tiles` (same carry-across-grid-steps
structure, one launch fewer on the hot loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def lane_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum along the last (lane) axis of a 2-D value,
    inside a kernel: log2(n) shift-add steps of lane rolls under an iota
    mask (Mosaic has no cumsum lowering)."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, x.ndim - 1), 0)
        shift *= 2
    return x


def _scan_kernel(x_ref, o_ref, carry_ref, *, exclusive: bool):
    """Grid step i scans block i of the last axis, offset by the carry."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]                                   # (rows, block_n)
    local = lane_cumsum(x)                           # bottom-up within block
    carry = carry_ref[...]                           # s_{p(v)}: all to the left
    if exclusive:
        o_ref[...] = carry + local - x               # top-down: shift by self
    else:
        o_ref[...] = carry + local
    carry_ref[...] = carry + local[:, -1:]


@functools.partial(jax.jit, static_argnames=("block_n", "exclusive", "interpret"))
def prefix_scan(x: jnp.ndarray, *, block_n: int = 512, exclusive: bool = False,
                interpret: bool = False) -> jnp.ndarray:
    """Cumulative sum along the last axis of a 2-D array (rows, n).

    block_n: VMEM tile width, rounded up to whole 128-lane vregs.
    """
    if x.ndim != 2:
        raise ValueError("prefix_scan expects (rows, n)")
    rows, n = x.shape
    if n == 0:                       # empty scan axis: cumsum of nothing
        return x
    block_n = -(-min(block_n, n) // 128) * 128
    n_pad = -(-n // block_n) * block_n
    xp = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    return pl.pallas_call(
        functools.partial(_scan_kernel, exclusive=exclusive),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((rows, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, n_pad), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), x.dtype)],
        interpret=interpret,
    )(xp)[:, :n]
