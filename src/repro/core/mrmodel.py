"""Generic MapReduce computation model (paper §2, Theorem 2.1), executable in JAX.

The paper models a MapReduce computation as rounds on a dynamic directed graph
G = (V, E):  each node v holds a state A_v(r) of items; every round, a
sequential function f maps A_v(r) to a set B_v(r) of (destination, item)
pairs; items are routed to their destinations, forming A_v(r+1).  Theorem 2.1:
if every node sends / keeps / receives at most M items per round, the
computation runs in the I/O-memory-bound MapReduce framework with unchanged
round complexity R and communication complexity C.

JAX adaptation (DESIGN.md §2): node states are *fixed-capacity mailboxes* —
pytrees of arrays with leading dims (V, M) plus a validity mask.  The M bound
the paper imposes on reducer I/O becomes the static mailbox capacity; routing
is a stable sort by destination plus a rank-addressed scatter (on a TPU mesh
the same routing is an ``all_to_all`` — see :mod:`repro.core.distributed`).
Overflow — the w.h.p. failure event in the paper's randomized algorithms — is
returned as an explicit drop counter instead of crashing a reducer, and can be
eliminated with the Theorem 4.2 queue discipline (:mod:`repro.core.queues`).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .costmodel import MRCost, RoundStats

Payload = Any  # pytree of arrays with leading dims (V, M, ...)

#: Back-compat alias: shuffle statistics are the per-round stats the
#: engine API accounts (see repro.core.engine).
ShuffleStats = RoundStats


class Mailbox(NamedTuple):
    """State A_v(r) for all nodes: ``payload`` leaves have shape (V, M, ...)."""

    payload: Payload
    valid: jnp.ndarray  # (V, M) bool

    @property
    def n_nodes(self) -> int:
        return self.valid.shape[0]

    @property
    def capacity(self) -> int:
        return self.valid.shape[1]


def make_mailbox(payload: Payload, valid: jnp.ndarray) -> Mailbox:
    return Mailbox(payload=payload, valid=valid.astype(bool))


def empty_like(box: Mailbox) -> Mailbox:
    return Mailbox(
        payload=jax.tree_util.tree_map(jnp.zeros_like, box.payload),
        valid=jnp.zeros_like(box.valid),
    )


def materialize_mailbox(dests: jnp.ndarray, payload: Payload,
                        flat_dest: jnp.ndarray, valid: jnp.ndarray,
                        rank: jnp.ndarray, n_nodes: int,
                        capacity: int) -> Tuple[Mailbox, jnp.ndarray]:
    """Placement tail of :func:`repro.core.kshuffle.kernel_shuffle`: keep
    items whose arrival ``rank`` fits ``capacity``, scatter payload +
    validity into the (V, capacity) mailbox (``mode='drop'`` discards
    out-of-range writes), and compute the per-source-node ``max_sent``
    stat.  The dense :func:`shuffle` places the same items in the same
    slots by gathering from its sorted order (DESIGN.md §7)."""
    n = flat_dest.shape[0]
    in_range = valid & (rank < capacity)
    dest_idx = jnp.where(in_range, flat_dest, -1)
    slot_idx = jnp.where(in_range, rank, capacity)

    def place(leaf: jnp.ndarray) -> jnp.ndarray:
        flat = leaf.reshape((n,) + leaf.shape[dests.ndim:])
        out = jnp.zeros((n_nodes, capacity) + flat.shape[1:], flat.dtype)
        return out.at[dest_idx, slot_idx].set(flat, mode="drop")

    new_payload = jax.tree_util.tree_map(place, payload)
    new_valid = jnp.zeros((n_nodes, capacity), bool).at[dest_idx, slot_idx].set(
        in_range, mode="drop")
    return (Mailbox(payload=new_payload, valid=new_valid),
            max_sent_of(dests, valid))


def max_sent_of(dests: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Most items any source node sent: rows of a (V, M, ...) ``dests``;
    a 1-D ``dests`` is one source (1)."""
    if dests.ndim >= 2 and valid.shape[0]:
        return jnp.max(jnp.sum(valid.reshape(dests.shape[0], -1), axis=1))
    # Empty (V, M) sends have no source nodes (reshape(-1) over a zero-size
    # leading dim is ill-posed anyway): max_sent = 0, matching the
    # reference backend's max(initial=0).
    return jnp.array(0 if dests.ndim >= 2 else 1, jnp.int32)


def fifo_sort(sort_key: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable sort of items by integer ``sort_key``: returns ``(order,
    sorted_key)``, the source index of each sorted position and the keys in
    sorted order.  Equal keys keep source order, so each key's run lists
    its items FIFO."""
    iota = jnp.arange(sort_key.shape[0], dtype=jnp.int32)
    sorted_key, order = jax.lax.sort((sort_key.astype(jnp.int32), iota),
                                     num_keys=1, is_stable=True)
    return order, sorted_key


def fifo_gather(order: jnp.ndarray, sorted_key: jnp.ndarray, leaves,
                n_groups: int, capacity: int):
    """Read (n_groups, capacity) FIFO boxes out of :func:`fifo_sort`'s order.

    Slot (g, r) holds the r-th item of key g, for r below both the number
    of such items and ``capacity``; other slots are zero and invalid.
    Returns (boxes, valid, counts) with ``counts[g]`` the items of key g.
    The sorted order makes every box a contiguous run, so placement reads
    each run with one gather and no rank travels back to source order."""
    n = order.shape[0]
    bounds = jnp.searchsorted(
        sorted_key, jnp.arange(n_groups + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    start, counts = bounds[:-1], bounds[1:] - bounds[:-1]
    slot = jnp.arange(capacity, dtype=jnp.int32)
    valid = slot[None, :] < jnp.minimum(counts, capacity)[:, None]
    if n == 0:
        return ([jnp.zeros((n_groups, capacity) + l.shape[1:], l.dtype)
                 for l in leaves], valid, counts)
    src = order[jnp.minimum(start[:, None] + slot[None, :], n - 1)]

    def place(leaf):
        ok = valid.reshape(valid.shape + (1,) * (leaf.ndim - 1))
        return jnp.where(ok, leaf[src], jnp.zeros((), leaf.dtype))
    return [place(l) for l in leaves], valid, counts


def shuffle(dests: jnp.ndarray, payload: Payload, n_nodes: int,
            capacity: int) -> Tuple[Mailbox, ShuffleStats]:
    """The Shuffle step: deliver item j to node ``dests[j]``.

    ``dests`` is any-shape int32; entries < 0 mark invalid (non-existent)
    items.  ``payload`` leaves share ``dests``'s leading shape.  Items are
    delivered in stable (source-order) FIFO order into per-node slots
    ``0..capacity-1``; items ranked past ``capacity`` at their destination are
    dropped and counted.

    This is the dense jnp implementation (one stable sort by destination,
    then a gather of each node's run into its slots) and the semantics
    oracle for the Pallas-composed counterpart,
    :func:`repro.core.kshuffle.kernel_shuffle` (DESIGN.md §7).
    """
    flat_dest = dests.reshape(-1)
    n = flat_dest.shape[0]
    valid = flat_dest >= 0
    # Invalid items sort to the end, as the sentinel node n_nodes.
    order, sorted_key = fifo_sort(jnp.where(valid, flat_dest, n_nodes))
    leaves, treedef = jax.tree_util.tree_flatten(payload)
    flat_leaves = [l.reshape((n,) + l.shape[dests.ndim:]) for l in leaves]
    boxes, box_valid, counts = fifo_gather(order, sorted_key, flat_leaves,
                                           n_nodes, capacity)
    stats = ShuffleStats(
        items_sent=jnp.sum(valid),
        max_sent=max_sent_of(dests, valid),
        max_received=jnp.max(counts).astype(jnp.int32),
        dropped=jnp.sum(jnp.maximum(counts - capacity, 0)),
    )
    box = Mailbox(payload=jax.tree_util.tree_unflatten(treedef, boxes),
                  valid=box_valid)
    return box, stats


# A round function f: (round_idx, node_ids, mailbox) -> (dests, payload).
# ``dests`` has shape (V, M_out); -1 entries are "no item".  Keeping item x at
# node v is expressed by dests[v, j] = v — exactly the paper's "keep" primitive.
RoundFn = Callable[[int, jnp.ndarray, Mailbox], Tuple[jnp.ndarray, Payload]]


def run_round(f: RoundFn, box: Mailbox, round_idx: int,
              cost: Optional[MRCost] = None,
              capacity: Optional[int] = None,
              engine=None) -> Tuple[Mailbox, ShuffleStats]:
    """Execute one round of the generic computation: apply f, then shuffle.

    Back-compat wrapper over the engine API (repro.core.engine): delegates to
    ``engine.run_round`` (default :class:`~repro.core.engine.LocalEngine`)
    and reports into the mutable ``cost`` adapter if given."""
    if engine is None:
        engine = _default_engine()
    new_box, stats = engine.run_round(f, box, round_idx, capacity=capacity)
    if cost is not None:
        cost.round(items_sent=int(stats.items_sent),
                   max_io=int(jnp.maximum(stats.max_sent, stats.max_received)))
    return new_box, stats


def run_rounds(f: RoundFn, box: Mailbox, n_rounds: int,
               cost: Optional[MRCost] = None,
               capacity: Optional[int] = None,
               engine=None) -> Mailbox:
    """Drive R rounds through an engine and raise on capacity overflow.

    Back-compat wrapper: ``engine.run_rounds`` returns (mailbox, CostAccum)
    without host syncs; this host-level driver additionally enforces the
    strict-model validity condition (no drops) and feeds ``cost``."""
    if engine is None:
        engine = _default_engine()
    box, accum = engine.run_rounds(f, box, n_rounds, capacity=capacity)
    engine.require_no_drops(accum, what=f"{n_rounds} rounds at capacity "
                            f"M={capacity or box.capacity}")
    if cost is not None:
        cost.absorb(accum)
    return box


def _default_engine():
    from .engine import default_engine    # deferred: engine imports mrmodel
    return default_engine()
