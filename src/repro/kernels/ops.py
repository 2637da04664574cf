"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in interpret mode (the kernel body
runs as traced jnp — bit-identical control flow to the TPU lowering); on a
TPU backend they compile to Mosaic; any other backend is an error.  The wrappers also do the shape hygiene
the kernels assume: GQA head broadcasting, head-dim padding to the 128-lane
MXU width, power-of-two padding for the bitonic network.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import bincount as _bincount
from . import bitonic_sort as _bitonic
from . import flash_attention as _flash
from . import prefix_scan as _prefix
from . import ssm_scan as _ssm


def _interpret() -> bool:
    """Compile with Mosaic on a TPU; interpret only on the CPU backend.  Any
    other backend has no Pallas TPU lowering, and interpreting there would
    hide that the kernels never ran on the device."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels cannot run on the {backend!r} backend")
    return backend == "cpu"


def prefix_scan(x: jnp.ndarray, *, exclusive: bool = False,
                block_n: int = 512) -> jnp.ndarray:
    """Blocked cumulative sum along the last axis of (rows, n)."""
    return _prefix.prefix_scan(x, block_n=block_n, exclusive=exclusive,
                               interpret=_interpret())


def bincount(ids: jnp.ndarray, n_buckets: int, *,
             block_t: int = 1024) -> jnp.ndarray:
    return _bincount.bincount(ids, n_buckets, block_t=block_t,
                              interpret=_interpret())


def bincount_tiles(tiles: jnp.ndarray, n_buckets: int):
    """Fused (counts, cross-tile exclusive prefix, in-tile bucket offsets)
    over (T, tile_n) ids — the radix shuffle's one-launch counting phase."""
    return _bincount.bincount_tiles(tiles, n_buckets, interpret=_interpret())


def bitonic_sort(keys: jnp.ndarray, values: jnp.ndarray):
    return _bitonic.bitonic_sort(keys, values, interpret=_interpret())


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jnp.ndarray:
    """q: (b, hq, s, d), k/v: (b, hkv, s, d) with hq % hkv == 0 (GQA).

    Returns (b, hq, s, d).  Pads s to the block size and d to 128 lanes.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires hq % hkv == 0, got {hq} % {hkv}")
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)

    d_pad = max(d, 128) if not _interpret() else d
    sq_pad = -(-sq // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k

    def pad(x, s_to, d_to):
        return jnp.pad(x, ((0, 0), (0, 0), (0, s_to - x.shape[2]),
                           (0, d_to - x.shape[3])))

    qp = pad(q, sq_pad, d_pad).reshape(b * hq, sq_pad, d_pad)
    kp = pad(k, sk_pad, d_pad).reshape(b * hq, sk_pad, d_pad)
    vp = pad(v, sk_pad, d_pad).reshape(b * hq, sk_pad, d_pad)
    if d_pad != d:
        # keep softmax scale consistent with the true head dim
        qp = qp * ((d_pad / d) ** 0.5)
    out = _flash.flash_attention(qp, kp, vp, causal=causal, block_q=block_q,
                                 block_k=block_k, kv_len=sk,
                                 interpret=_interpret())
    return out.reshape(b, hq, sq_pad, d_pad)[:, :, :sq, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ssm_scan_ad(a: jnp.ndarray, x: jnp.ndarray, block_t: int) -> jnp.ndarray:
    return _ssm.ssm_scan(a, x, block_t=block_t, interpret=_interpret())


def _ssm_scan_fwd(a, x, block_t):
    h = _ssm.ssm_scan(a, x, block_t=block_t, interpret=_interpret())
    return h, (a, h)


def _ssm_scan_bwd(block_t, res, dh):
    """Adjoint of h_t = a_t h_{t-1} + x_t:
        g_t = dh_t + a_{t+1} g_{t+1}   (reverse scan — same kernel, flipped)
        dx_t = g_t,   da_t = g_t * h_{t-1}.
    """
    a, h = res
    a_next = jnp.concatenate([a[:, 1:], jnp.ones_like(a[:, :1])], axis=1)
    g = jnp.flip(_ssm.ssm_scan(jnp.flip(a_next, axis=1),
                               jnp.flip(dh, axis=1), block_t=block_t,
                               interpret=_interpret()), axis=1)
    h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)
    return g * h_prev, g


_ssm_scan_ad.defvjp(_ssm_scan_fwd, _ssm_scan_bwd)


def ssm_scan(a: jnp.ndarray, x: jnp.ndarray, *,
             block_t: int = 256) -> jnp.ndarray:
    """Differentiable blocked linear-recurrence scan (custom VJP: the
    adjoint is the same recurrence run backwards — the funnel transposed)."""
    return _ssm_scan_ad(a, x, block_t)
