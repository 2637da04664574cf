"""TPU-native (shard_map) realizations of the paper's primitives.

Each function here is the collective counterpart of a `repro.core` algorithm
(DESIGN.md §2 table):

  shuffle_alltoall      -- the Shuffle step over a mesh axis (Thm 2.1);
                           the routing layer of MoE expert dispatch.
  funnel_allreduce      -- a two-level invisible funnel with f = + :
                           reduce-scatter (level-1 fan-in, d = |inner axis|)
                           then cross-pod psum (level-2), then all-gather.
                           The multi-pod gradient reduction.
  softmax_merge         -- the funnel under the (max, sum-exp) semigroup:
                           merges attention partials across a sequence-sharded
                           KV axis (flash-decode combine).
  sharded_sample_sort   -- §4.3 sample sort as one local sort + pivot
                           all-gather + bucket all_to_all + local merge.
  segment_scatter_add   -- funnel-write with f = + for many-to-one writes
                           (vocab-sharded embedding-gradient accumulation).

All are pure jnp + lax collectives so they can be used inside pjit/shard_map
and lowered in the multi-pod dry-run.  Single-device semantics (axis size 1)
degenerate to the local operation, which is how the CPU tests validate them
against the faithful `repro.core` implementations.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .mrmodel import fifo_gather, fifo_sort


# ---------------------------------------------------------------------------
# Shuffle (Theorem 2.1) — keyed all_to_all routing
# ---------------------------------------------------------------------------

class ShuffleOut(NamedTuple):
    payload: Any               # (n_shards, capacity, ...) per receiving shard
    valid: jnp.ndarray         # (n_shards, capacity)
    dropped: jnp.ndarray       # scalar — items beyond per-pair capacity


def shuffle_alltoall(dests: jnp.ndarray, payload: Any, axis_name: str,
                     capacity: int) -> ShuffleOut:
    """Route each local item to the shard named by ``dests`` (< 0 = none).

    Must be called inside shard_map over ``axis_name``.  ``capacity`` bounds
    items per (sender, receiver) pair — the M of the I/O-bound model; the
    send buffer is (n_shards, capacity) so each shard sends and receives at
    most n_shards * capacity items."""
    n_shards = lax.psum(1, axis_name)
    flat_dests = dests.reshape(-1)
    n = flat_dests.shape[0]
    valid = (flat_dests >= 0) & (flat_dests < n_shards)
    order, sorted_key = fifo_sort(jnp.where(valid, flat_dests, n_shards))
    leaves, treedef = jax.tree_util.tree_flatten(payload)
    boxes, _, counts = fifo_gather(
        order, sorted_key,
        [l.reshape((n,) + l.shape[dests.ndim:]) for l in leaves],
        n_shards, capacity)
    send = jax.tree_util.tree_unflatten(treedef, boxes)
    dropped = jnp.sum(jnp.maximum(counts - capacity, 0))

    def a2a(leaf):
        return lax.all_to_all(leaf, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)

    recv = jax.tree_util.tree_map(a2a, send)
    # Every box is a FIFO prefix: send its fill count and rebuild the mask
    # on arrival (an all_to_all of the mask itself compiles in minutes and
    # gigabytes of host memory at chip sizes).
    recv_fill = a2a(jnp.minimum(counts, capacity))
    recv_mask = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                 < recv_fill[:, None])
    return ShuffleOut(payload=recv, valid=recv_mask,
                      dropped=lax.psum(dropped, axis_name))


def keyed_hop(dests: jnp.ndarray, leaves: Sequence[jnp.ndarray],
              axis_name: str, n_nodes: int
              ) -> Tuple[jnp.ndarray, list]:
    """Phase 1 of the sharded Shuffle: the keyed ``all_to_all`` hop.

    Routes every local (dest, *leaves) item to the shard that owns node
    ``dest`` (contiguous ownership: shard s owns [s*V/n, (s+1)*V/n)) with
    per-pair capacity equal to the local item count, so the hop itself is
    lossless — overflow can only happen at the phase-2 scatter, the same
    event the local backends count.  Must be called inside shard_map over
    ``axis_name``.

    Returns ``(local_dest, recv_flat)``: the shard-local destination of
    each arrival (-1 = empty slot) and the flattened received leaves, in
    source-shard-major order — which, with contiguous sources, preserves
    the global flattened-source FIFO order the scatter relies on
    (DESIGN.md §13).
    """
    n_shards = lax.psum(1, axis_name)
    local_v = n_nodes // n_shards
    flat_dest = dests.reshape(-1).astype(jnp.int32)
    n_local = flat_dest.shape[0]
    flat_leaves = [l.reshape((n_local,) + l.shape[dests.ndim:])
                   for l in leaves]
    owner = jnp.where(flat_dest >= 0,
                      jnp.clip(flat_dest, 0, n_nodes - 1) // local_v,
                      -1)
    routed = shuffle_alltoall(owner, (flat_dest, flat_leaves), axis_name,
                              capacity=n_local)
    recv_dest, recv_leaves = routed.payload
    recv_valid = routed.valid.reshape(-1)
    shard = lax.axis_index(axis_name)
    local_dest = jnp.where(recv_valid,
                           recv_dest.reshape(-1) - shard * local_v,
                           -1)
    recv_flat = [rl.reshape((-1,) + rl.shape[2:]) for rl in recv_leaves]
    return local_dest, recv_flat


# ---------------------------------------------------------------------------
# Invisible funnel with f = + (Theorem 3.2) — hierarchical gradient reduction
# ---------------------------------------------------------------------------

def funnel_allreduce(x: jnp.ndarray, inner_axis: str,
                     outer_axis: Optional[str] = None,
                     scatter_dim: int = 0) -> jnp.ndarray:
    """Two-level funnel all-reduce: reduce-scatter over the (fast, wide)
    inner axis, psum over the (slow, narrow) outer axis on 1/|inner| of the
    data, then all-gather.  Versus a flat psum over both axes this moves
    |inner|x less data over the outer (inter-pod DCN/ICI) links — the paper's
    C/B term attacked by funnel fan-in (DESIGN.md §5)."""
    if x.shape[scatter_dim] % lax.psum(1, inner_axis) != 0:
        y = lax.psum(x, inner_axis)
        if outer_axis is not None:
            y = lax.psum(y, outer_axis)
        return y
    shard = lax.psum_scatter(x, inner_axis, scatter_dimension=scatter_dim,
                             tiled=True)
    if outer_axis is not None:
        shard = lax.psum(shard, outer_axis)
    return lax.all_gather(shard, inner_axis, axis=scatter_dim, tiled=True)


def segment_scatter_add(dests: jnp.ndarray, values: jnp.ndarray,
                        n_cells: int) -> jnp.ndarray:
    """Local funnel-write with f=+ : combine many-to-one writes into cells.
    (On TPU XLA lowers scatter-add to a sorted segment reduction — the
    invisible funnel folded into one kernel.)"""
    ok = dests >= 0
    idx = jnp.where(ok, dests, n_cells)
    out_shape = (n_cells,) + values.shape[dests.ndim:]
    zeros = jnp.zeros(out_shape, values.dtype)
    flat_idx = idx.reshape(-1)
    flat_val = values.reshape((-1,) + values.shape[dests.ndim:])
    return zeros.at[flat_idx].add(
        jnp.where(ok.reshape((-1,) + (1,) * (flat_val.ndim - 1)), flat_val, 0),
        mode="drop")


# ---------------------------------------------------------------------------
# (max, sum-exp) semigroup merge — sequence-sharded attention combine
# ---------------------------------------------------------------------------

class AttnPartial(NamedTuple):
    m: jnp.ndarray             # running max of logits        (..., )
    l: jnp.ndarray             # running sum of exp(logit-m)  (..., )
    o: jnp.ndarray             # unnormalized output          (..., d)


def softmax_merge_pair(a: AttnPartial, b: AttnPartial) -> AttnPartial:
    """The commutative semigroup op underlying flash attention/decoding."""
    m = jnp.maximum(a.m, b.m)
    ea = jnp.exp(a.m - m)
    eb = jnp.exp(b.m - m)
    return AttnPartial(m=m, l=a.l * ea + b.l * eb,
                       o=a.o * ea[..., None] + b.o * eb[..., None])


def softmax_merge_axis(p: AttnPartial, axis_name: str) -> jnp.ndarray:
    """Funnel-combine attention partials across a mesh axis and normalize.
    Two collectives realize the semigroup: pmax for m, psum for the rescaled
    (l, o) — a depth-1 funnel, optimal on an ICI torus."""
    m_g = lax.pmax(p.m, axis_name)
    scale = jnp.exp(p.m - m_g)
    l_g = lax.psum(p.l * scale, axis_name)
    o_g = lax.psum(p.o * scale[..., None], axis_name)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# §4.3 sample sort, sharded
# ---------------------------------------------------------------------------

class ShardedSortOut(NamedTuple):
    values: jnp.ndarray        # (capacity,) per shard, ascending among valid
    valid: jnp.ndarray         # (capacity,)
    dropped: jnp.ndarray


def sharded_sample_sort(x: jnp.ndarray, axis_name: str,
                        oversample: int = 8,
                        slack: float = 2.0) -> ShardedSortOut:
    """Distributed sample sort over one mesh axis (inside shard_map).

    1. local sort (the TPU path uses the bitonic Pallas kernel);
    2. every shard contributes ``oversample`` evenly-spaced local samples;
       all-gather -> global pivot frontier (replicated; this is the paper's
       sqrt(N)-pivot brute-force stage, except the frontier fits in VMEM so
       one round suffices);
    3. multisearch (vectorized searchsorted) buckets each item by shard;
    4. all_to_all shuffle with per-pair capacity slack * n_local / n_shards;
    5. local merge (sort of received buffer).

    Output: per-shard sorted runs; shard i holds keys in pivot range i.
    """
    n_local = x.shape[0]
    n_shards = lax.psum(1, axis_name)
    xs = jnp.sort(x)
    step = max(1, n_local // oversample)
    samples = xs[::step][:oversample]
    all_samples = lax.all_gather(samples, axis_name, tiled=True)
    pivots = jnp.sort(all_samples)
    # n_shards-1 splitters, evenly spaced in the sampled distribution
    k = all_samples.shape[0]
    splitter_idx = (jnp.arange(1, n_shards) * k) // n_shards
    splitters = pivots[splitter_idx]
    bucket = jnp.searchsorted(splitters, xs, side="right").astype(jnp.int32)
    cap = int(slack * n_local / max(1, n_shards)) + 1
    out = shuffle_alltoall(bucket, xs, axis_name, capacity=cap)
    vals = out.payload.reshape(-1)
    mask = out.valid.reshape(-1)
    big = (jnp.finfo(x.dtype).max if jnp.issubdtype(x.dtype, jnp.floating)
           else jnp.iinfo(x.dtype).max)
    filled = jnp.where(mask, vals, big)
    order = jnp.argsort(filled)
    return ShardedSortOut(values=filled[order],
                          valid=mask[order],
                          dropped=out.dropped)
