"""Kernel-backed Shuffle step: a multi-tile radix route, on Pallas.

Every algorithm in the paper bottoms out in the same primitive — the
capacity-bounded shuffle round.  Theorem 4.2's queue discipline makes the
structure explicit as a two-phase "invisible funnel": first send the *counts*
(how many items target each reducer), then route items to reserved slots.
:func:`kernel_shuffle` is that dataflow as a **multi-tile radix shuffle**
composed from the Pallas kernels in :mod:`repro.kernels`:

    dests, tiled (T, tile) ──► bincount_tiles ──► C  per-tile counts
                                              ──► P  cross-tile excl. prefix
                                              ──► F  in-tile bucket offsets
                                                  (ONE fused launch: the
                                                   paper's "send the counts")
    segmented keys dest·tile + local_src ──► bitonic_sort (T local networks,
                                              one gridded launch)
    rank = P[tile, dest] + (sorted position − F[tile, dest])   global FIFO
    rank-addressed scatter ──► (V, capacity) mailbox slots

The bitonic network survives only as the *within-tile* local sort (the
paper's "one reducer sorts its bucket"), so the composite key is segmented
per tile — ``dest * tile + local_src`` with local_src < tile — and stays
int32 even when the old global key ``dest * n + src`` would overflow.  The
old size cliffs (single-VMEM-tile ``n <= 2^18``; int32 key space
``n_nodes·n + n − 1 < 2^31 − 1``) are gone: the tile count T is unbounded,
so entry-level shapes route through the kernel (see :func:`kernel_fits`
for the guards that remain, all VMEM or HBM bounds of one call).

The result is **bit-identical** to the dense :func:`repro.core.mrmodel.
shuffle` — same mailbox payload/validity, same :class:`RoundStats` (including
the drop count), same FIFO-within-source order — which the conformance suite
(``tests/test_conformance.py``) and the differential fuzz suite
(``tests/test_kernel_shuffle.py``, ``tests/test_properties.py``) pin.

On a TPU the kernels compile with Mosaic; on the CPU backend (the test
suite) they run with ``interpret=True`` — the kernel bodies execute as
traced jnp with the identical control flow the Mosaic lowering compiles, so
the parity tests cover the TPU code path's semantics; only the timing
differs.  ``tests/test_tpu_compile.py`` compiles them for a described v5e.
Select this path per engine with ``LocalEngine(shuffle_impl="kernel")`` /
``get_engine("pallas")``.

    >>> import numpy as np, jax.numpy as jnp
    >>> box, stats = kernel_shuffle(jnp.array([1, 0, 1, 1], jnp.int32),
    ...                             jnp.arange(4.0), 2, 2, tile_n=2)
    >>> np.asarray(box.valid).tolist()     # node 1 overflows: FIFO keeps
    [[True, False], [True, True]]
    >>> int(stats.dropped)                 # ...the first 2, drops the third
    1
    >>> kernel_fits((1 << 18) + 1, 64)     # past the old single-tile cliff
    True
    >>> kernel_fits(300000, 8191)          # past the old int32-key cliff
    True
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as _kops
from ..kernels.bincount import MAX_BUCKETS
from ..kernels.bitonic_sort import MAX_ROW_WIDTH
from .costmodel import RoundStats
from .mrmodel import Mailbox, Payload, materialize_mailbox

#: the OLD single-tile cliff (PR 3-7): the bitonic network ran the whole row
#: as one VMEM tile of at most this many elements; kernel_fits no longer
#: depends on n at all.
_MAX_SORT_N = 1 << 18
#: default within-tile sort width (one bitonic network per tile)
_TILE_N = 4096
#: total-element budget for each (T, n_nodes+1) count matrix in HBM (three
#: such int32 matrices at the edge take 384 MiB of a v5e's 16 GB)
_COUNTS_BUDGET = 1 << 25


class RouteLog:
    """Host-side counters of the engine-level kernel-vs-dense routing
    decision (``LocalEngine``/``ShardedEngine`` with ``shuffle_impl=
    "kernel"``).  Incremented when the per-call :func:`kernel_fits`
    predicate is evaluated — once per eager call, once per traced shape
    under jit/scan — so tests and benches can assert the kernel path was
    actually *taken* (``dense == 0``) rather than silently falling back.

    Each kernel-capable engine owns its own instance (``engine.route_log``)
    so concurrent services on different engines never interleave counts;
    routing decisions also surface as ``shuffle.route`` events on an
    attached :class:`repro.obs.Tracer`.

    ``overlapped`` counts rounds the ShardedEngine scheduled through the
    double-buffered path (DESIGN.md §13) — a scheduling counter, not a
    routing one, so :meth:`snapshot` (the kernel-vs-dense pair the parity
    tests compare) deliberately excludes it.
    """

    __slots__ = ("kernel", "dense", "overlapped")

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.kernel = 0
        self.dense = 0
        self.overlapped = 0

    def snapshot(self) -> Tuple[int, int]:
        return (self.kernel, self.dense)


def _misfit(n: int, n_nodes: int, tile_n: Optional[int]) -> Optional[str]:
    """Why a shuffle of ``n`` items into ``n_nodes`` nodes cannot take the
    kernel path, or None when it can."""
    tile = _TILE_N if tile_n is None else tile_n
    if tile < 1:
        raise ValueError(f"tile_n must be >= 1, got {tile_n}")
    if n_nodes > MAX_BUCKETS:
        return (f"n_nodes={n_nodes} exceeds the bincount_tiles one-hot VMEM "
                f"budget ({MAX_BUCKETS} buckets); use the dense shuffle "
                f"(LocalEngine(shuffle_impl='dense')) for this node count")
    if tile > MAX_ROW_WIDTH:
        return (f"tile_n={tile} exceeds the bitonic_sort row-block VMEM "
                f"budget ({MAX_ROW_WIDTH} keys per row)")
    n_tiles = -(-n // tile) if n else 1
    if n_tiles * (n_nodes + 1) > _COUNTS_BUDGET:
        return (f"tile-count matrix {n_tiles}x{n_nodes + 1} exceeds the "
                f"counts budget ({_COUNTS_BUDGET}); use the dense shuffle "
                f"(LocalEngine(shuffle_impl='dense')) for this size")
    return None


def kernel_fits(n: int, n_nodes: int, tile_n: Optional[int] = None) -> bool:
    """Whether a shuffle of ``n`` flattened items into ``n_nodes`` nodes fits
    the multi-tile kernel path's guards.

    The old cliffs — ``n`` past one VMEM tile, composite key past int32 —
    are gone: the sort is tiled and the keys are segmented per tile.  The
    guards that remain are functions of one *call's* shape, each a bound
    the TPU compiler enforces (``tests/test_tpu_compile.py`` compiles at
    the edges):

    - ``n_nodes`` within the one-hot VMEM budget of ``bincount_tiles``
      (``repro.kernels.bincount.MAX_BUCKETS``);
    - an explicit ``tile_n`` within one VMEM row block of ``bitonic_sort``
      (the default tile is ``_TILE_N``);
    - the (T, n_nodes+1) count matrices within ``_COUNTS_BUDGET`` elements
      (T = ceil(n / tile)).

    Together the first two keep the segmented keys ``(n_nodes+1)·tile``
    inside int32.  In a shape-scheduled program (DESIGN.md §9) the predicate
    is re-derived per stage from that stage's (V_r, M_r) footprint — both
    ``LocalEngine(shuffle_impl="kernel")`` and ``ShardedEngine``'s
    per-shard scatter route each call through it.  The strict
    :func:`kernel_shuffle` guards raise on exactly ``not kernel_fits(...)``
    — one predicate, two policies.
    """
    return _misfit(n, n_nodes, tile_n) is None


def _check_fits(n: int, n_nodes: int, tile_n: Optional[int]) -> None:
    why = _misfit(n, n_nodes, tile_n)
    if why is not None:
        raise ValueError(f"kernel_shuffle: {why}")


def kernel_shuffle(dests: jnp.ndarray, payload: Payload, n_nodes: int,
                   capacity: int, *, tile_n: Optional[int] = None
                   ) -> Tuple[Mailbox, RoundStats]:
    """Pallas-composed Shuffle: deliver item j to node ``dests[j]``.

    Contract identical to :func:`repro.core.mrmodel.shuffle` (the dense
    oracle): ``dests`` any-shape int32 with entries in [-1, n_nodes), < 0 =
    "no item"; ``payload`` leaves share ``dests``'s leading shape; items are
    delivered FIFO in flattened source order into slots 0..capacity-1 and
    items ranked past ``capacity`` at their destination are dropped and
    counted.  Returns the same (Mailbox, RoundStats) bit-for-bit.

    Composition (see module docstring): the flattened sources are cut into
    T source-order tiles; one fused ``kernels.bincount_tiles`` launch
    yields per-tile counts, the cross-tile exclusive prefix (items each
    bucket received from earlier tiles) and in-tile bucket offsets; one
    gridded ``kernels.bitonic_sort`` launch stably sorts every tile on the
    segmented key ``dest·tile + local_src``; each item's global FIFO
    arrival rank is then ``cross_tile_prefix + in-tile rank``, and a
    rank-addressed scatter materializes the (V, capacity) mailbox.

    ``tile_n`` overrides the default tile width (testing/tuning knob).
    """
    dests = jnp.asarray(dests)
    flat_dest = dests.reshape(-1).astype(jnp.int32)
    n = flat_dest.shape[0]
    _check_fits(n, n_nodes, tile_n)
    valid = flat_dest >= 0

    if n == 0:
        counts = jnp.zeros((n_nodes,), jnp.int32)
        rank = jnp.zeros((0,), jnp.int32)
    else:
        tile = _TILE_N if tile_n is None else tile_n
        n_tiles = -(-n // tile)
        # Source-order tiling; the tail pads with the "no item" sentinel.
        dtile = jnp.pad(flat_dest, (0, n_tiles * tile - n),
                        constant_values=-1).reshape(n_tiles, tile)
        # Phase 1 — counts, fused: per-tile fan-in C, cross-tile exclusive
        # prefix P (Thm 4.2 R1 "send the counts": how many same-dest items
        # earlier tiles hold), and in-tile bucket offsets F, one launch.
        C, P, F = _kops.bincount_tiles(dtile, n_nodes)
        counts = P[-1] + C[-1]                       # global per-node fan-in
        # Phase 2 — tile-local stable sort on segmented keys: equal dests
        # keep local source order; invalid items take the sentinel bucket
        # n_nodes and sort last, below the int32-max padding.
        lsrc = jnp.broadcast_to(jnp.arange(tile, dtype=jnp.int32),
                                (n_tiles, tile))
        key = jnp.where(dtile >= 0, dtile, n_nodes) * tile + lsrc
        sorted_key, sorted_src = _kops.bitonic_sort(key, lsrc)
        sorted_dest = sorted_key // tile             # in [0, n_nodes]
        # Phase 3 — global FIFO rank: in-tile rank (sorted position minus
        # the dest run's first in-tile slot) plus the cross-tile prefix.
        # Sentinel columns close both tables for invalid/padded items.
        first = jnp.concatenate([F, F[:, -1:] + C[:, -1:]], axis=1)
        cross = jnp.concatenate([P, jnp.zeros((n_tiles, 1), P.dtype)],
                                axis=1)
        pos = jnp.broadcast_to(jnp.arange(tile, dtype=jnp.int32),
                               (n_tiles, tile))
        rank_sorted = (pos - jnp.take_along_axis(first, sorted_dest, axis=1)
                       + jnp.take_along_axis(cross, sorted_dest, axis=1))
        # Phase 4 — scatter ranks back to source order (tile-local inverse
        # permutation), then drop the tail padding.
        rows = jnp.broadcast_to(
            jnp.arange(n_tiles, dtype=jnp.int32)[:, None], (n_tiles, tile))
        rank = (jnp.zeros((n_tiles, tile), jnp.int32)
                .at[rows, sorted_src].set(rank_sorted).reshape(-1)[:n])

    # Materialize through the tail shared with the dense shuffle; only the
    # remaining stats come from the kernel-computed counts.
    box, max_sent = materialize_mailbox(dests, payload, flat_dest, valid,
                                        rank, n_nodes, capacity)
    stats = RoundStats(
        items_sent=jnp.sum(counts),
        max_sent=max_sent,
        max_received=jnp.max(counts).astype(jnp.int32) if n_nodes
        else jnp.int32(0),
        dropped=jnp.sum(jnp.maximum(counts - capacity, 0)),
    )
    return box, stats
