"""Unified MREngine API: one round-program abstraction, pluggable backends.

The paper's Theorem 2.1 defines a single round-based computation model that
every algorithm in §3-§4 compiles into: each round, node v applies a
sequential function f to its state A_v(r), emitting (destination, item)
pairs; the shuffle routes items to form A_v(r+1).  This module is that model
*as an API*: an algorithm is a :class:`RoundProgram` — a round function plus
a round count and capacity — and an :class:`MREngine` executes it.  Three
interchangeable backends (DESIGN.md §2):

  ================== ========================== ===========================
  backend            substrate                  role
  ================== ========================== ===========================
  ReferenceEngine    numpy, per-item host loop  semantics oracle for tests
  LocalEngine        jnp, dense mailboxes       jit/lax.scan round loops
  ShardedEngine      shard_map + all_to_all     same program over a mesh axis
  ================== ========================== ===========================

Orthogonally to the backend, the Shuffle hot loop has two implementations
(``shuffle_impl=``): the ``"dense"`` jnp sort-and-gather of
:func:`repro.core.mrmodel.shuffle`, and the ``"kernel"`` Pallas composition
of :func:`repro.core.kshuffle.kernel_shuffle` (bincount → prefix_scan →
bitonic_sort; DESIGN.md §7).  ``get_engine("pallas")`` is the registered
alias for a kernel-backed :class:`LocalEngine`; ``ShardedEngine`` accepts
the same choice for its per-shard local scatter.  Both implementations are
bit-identical — the kernel path is a performance substitution, never a
semantic one.

A complete round trip through the API::

    >>> import numpy as np
    >>> from repro.core.engine import get_engine
    >>> eng = get_engine("local")
    >>> box, stats = eng.shuffle(np.array([1, 0, 1, 1], np.int32),
    ...                          np.arange(4.0, dtype=np.float32),
    ...                          n_nodes=2, capacity=2)
    >>> np.asarray(box.valid).tolist()     # node 1 overflows: slot-FIFO keeps
    [[True, False], [True, True]]
    >>> int(stats.dropped)                 # ...the first 2, drops the third
    1
    >>> kbox, kstats = get_engine("pallas").shuffle(
    ...     np.array([1, 0, 1, 1], np.int32),
    ...     np.arange(4.0, dtype=np.float32), n_nodes=2, capacity=2)
    >>> bool(np.array_equal(np.asarray(box.payload), np.asarray(kbox.payload)))
    True

All three implement identical shuffle semantics — stable source-order FIFO
delivery into per-node slots 0..capacity-1, items ranked past ``capacity``
dropped and counted — so a round program yields bit-identical mailboxes and
stats on every backend (``ShardedEngine`` included, at any axis size: the
first all_to_all hop is lossless and sources are contiguous per shard, so
global FIFO order is preserved).

Cost accounting is functional: engines return :class:`RoundStats` per round
and fold them into a :class:`CostAccum` value.  Both are pytrees of scalars,
so a ``LocalEngine`` round loop jits and scans with zero host syncs; the
mutable :class:`MRCost` survives only as a host-side reporting adapter
(``MRCost.absorb``).

Complete algorithms enter through the plan/compile/execute split
(DESIGN.md §8): a ``*_plan`` builder emits the static round schedule,
``engine.compile(plan)`` lowers it once into a cached
:class:`~repro.core.api.Executable`, and ``exe.batch(B)`` vmaps the whole
round program for batched serving.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType

from .costmodel import CostAccum, MRCost, RoundStats
from .mrmodel import Mailbox, Payload, RoundFn, make_mailbox
from .mrmodel import shuffle as _dense_shuffle
from ..obs import NULL_TRACER, round_event as _round_event
from ..obs.trace import annotate


class RoundProgram(NamedTuple):
    """A Theorem 2.1 computation: R applications of one round function.

    ``fn`` follows the :data:`repro.core.mrmodel.RoundFn` contract
    ``f(round_idx, node_ids, mailbox) -> (dests, payload)`` with dests of
    shape (V, M_out); -1 entries mean "no item", ``dests[v, j] = v`` is the
    paper's "keep".  Under ``LocalEngine`` scan execution ``round_idx`` may
    be a traced int32 — branch on it with ``jnp.where``, not Python ``if``.
    """

    fn: RoundFn
    n_rounds: int
    capacity: Optional[int] = None
    #: target mailbox node count per round (None = inherit the entry shape);
    #: with ``capacity`` this is the program's physical footprint (V_r, M_r)
    n_nodes: Optional[int] = None


class MREngine:
    """Interface over the Theorem 2.1 round semantics.

    Subclasses provide :meth:`shuffle` — the capacity-bounded Shuffle step
    with the bit-identical contract of DESIGN.md §2 (flattened-source-order
    FIFO into slots 0..capacity-1, overflow dropped and counted) —
    while ``run_round`` / ``run_rounds`` / ``run_program`` /
    ``run_stages`` drive complete computations on top of it and account
    costs functionally (:class:`RoundStats` per round folded into a
    :class:`CostAccum`).  Concrete backends: :class:`ReferenceEngine`
    (numpy oracle), :class:`LocalEngine` (dense jnp; ``"pallas"`` alias =
    kernel shuffle), :class:`ShardedEngine` (``shard_map``/``all_to_all``).
    """

    name = "abstract"
    #: whether whole round programs may be wrapped in one ``jax.jit``
    jittable = False
    #: whether whole round programs may be ``jax.vmap``-ed (Executable.batch)
    vmappable = False
    #: bound on the per-engine plan/shuffle cache (see BoundedCache)
    cache_size = 128
    _cache = None
    #: observability hook (repro.obs, DESIGN.md §12): a no-op NullTracer by
    #: default; an attached live Tracer records round/compile/route events
    #: at host boundaries only (its events drop at jax trace time, so
    #: jitted round programs lower identically either way)
    tracer = NULL_TRACER

    def __init__(self, tracer=None):
        if tracer is not None:
            self.tracer = tracer

    # -- plan/compile/execute split (repro.core.plan / repro.core.api) -------
    def _ensure_cache(self):
        if self._cache is None:
            from .api import BoundedCache
            self._cache = BoundedCache(self.cache_size)
        return self._cache

    @staticmethod
    def plan_key(plan):
        """The cache key a plan compiles under.  The declared shape
        schedule is part of the identity: two plans that differ only in
        per-stage (V_r, M_r) footprints must not share a compiled
        executable (DESIGN.md §9)."""
        return ("plan", plan.fingerprint, plan.shape_fingerprint)

    def plan_cached(self, plan) -> bool:
        """Whether ``compile(plan)`` would be a cache hit right now — a
        read-only probe (no counters, no LRU touch) for admission control:
        the serving layer asks it before admitting a cold fingerprint that
        would evict a hot executable (DESIGN.md §10)."""
        return self.plan_key(plan) in self._ensure_cache()

    def compile(self, plan):
        """Lower a :class:`~repro.core.plan.Plan` onto this backend.

        Returns the cached :class:`~repro.core.api.Executable` when an
        equal-fingerprint plan was compiled before (a cache hit performs
        zero retraces — the jitted round program is reused as-is); the
        bounded cache evicts LRU and reports through :meth:`cache_info`.
        """
        from .api import Executable
        cache = self._ensure_cache()
        key = self.plan_key(plan)
        exe = cache.lookup(key)
        tr = self.tracer
        if exe is None:
            exe = cache.store(key, Executable(plan, self))
            if tr.enabled:
                tr.event("cache.miss", plan=plan.name, backend=self.name)
                tr.count("plan_cache.misses")
        elif tr.enabled:
            tr.event("cache.hit", plan=plan.name, backend=self.name)
            tr.count("plan_cache.hits")
        return exe

    def cache_info(self):
        """Hit/miss/eviction counters of this engine's bounded cache (plan
        executables plus, on ShardedEngine, per-shape shuffle lowerings)."""
        return self._ensure_cache().info()

    # -- backend layout hooks ------------------------------------------------
    def aligned_nodes(self, n_nodes: int) -> int:
        """Round a node count up to this backend's layout granularity."""
        return max(1, int(n_nodes))

    def node_ids(self, n_nodes: int) -> jnp.ndarray:
        return jnp.arange(n_nodes, dtype=jnp.int32)

    # -- the Shuffle step ----------------------------------------------------
    def shuffle(self, dests, payload: Payload, n_nodes: int,
                capacity: int) -> Tuple[Mailbox, RoundStats]:
        """Deliver item j to node ``dests[j]`` (< 0 = no item; entries must
        lie in [-1, n_nodes)).  FIFO by flattened source order; items ranked
        past ``capacity`` at their destination are dropped and counted in
        ``RoundStats.dropped`` — every backend must report the identical
        mailbox, drop set, and stats (tests/test_conformance.py)."""
        raise NotImplementedError

    # -- round drivers -------------------------------------------------------
    def run_round(self, f: RoundFn, box: Mailbox, round_idx,
                  capacity: Optional[int] = None,
                  n_nodes: Optional[int] = None
                  ) -> Tuple[Mailbox, RoundStats]:
        """One round: apply f at every node, then shuffle.

        ``n_nodes`` sets the target mailbox node count — a *shape-change
        round* when it differs from ``box.n_nodes`` (the paper's tree
        algorithms shrink their live node set geometrically per level;
        DESIGN.md §9).  ``f`` must then emit destinations in the target's
        compact numbering [0, n_nodes).  None keeps the current shape.

        ``f`` runs inside the ``mr.round`` named scope, and an eager round
        inside an ``engine.round`` profiler annotation."""
        cap = capacity if capacity is not None else box.capacity
        V = n_nodes if n_nodes is not None else box.n_nodes
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        with annotate("engine.round", round=round_idx):
            with jax.named_scope("mr.round"):
                dests, payload = f(round_idx, self.node_ids(box.n_nodes), box)
            out_box, stats = self.shuffle(dests, payload, V, cap)
        if tr.enabled:
            # The event drops silently under jit/scan tracing, so the
            # jitted round loop is untouched; on eager rounds reading the
            # stats is a host sync — the opt-in cost of tracing.
            _round_event(tr, t0, self.name, round_idx, V, cap, stats)
        return out_box, stats

    def run_rounds(self, f: RoundFn, box: Mailbox, n_rounds: int,
                   capacity: Optional[int] = None,
                   accum: Optional[CostAccum] = None,
                   n_nodes: Optional[int] = None,
                   checkpointer=None, round_offset: int = 0,
                   early_dests: bool = False
                   ) -> Tuple[Mailbox, CostAccum]:
        """Drive R rounds, returning the final mailbox and accumulated cost.

        ``checkpointer`` (a :class:`repro.core.recovery.Checkpointer`)
        activates the ``checkpoint_every`` policy: after each round the
        ``{"box", "accum"}`` state is offered to ``maybe_save`` under the
        global round index ``round_offset + r + 1`` — the round-boundary
        snapshot recovery replays from (DESIGN.md §11).

        ``early_dests`` is the stage's declared scheduling-legality bit
        (:class:`repro.core.plan.PlanStage`, DESIGN.md §13): True promises
        the round function's destinations depend only on node ids and the
        static schedule, which lets :class:`ShardedEngine` double-buffer
        the hop of round r+1 under the reducer compute of round r.  The
        flag never changes results — backends without an overlapped
        scheduler (this base loop included) simply ignore it."""
        acc = accum if accum is not None else CostAccum.zero()
        for r in range(n_rounds):
            box, stats = self.run_round(f, box, r, capacity, n_nodes=n_nodes)
            acc = acc.add_round_stats(stats)
            if checkpointer is not None:
                checkpointer.maybe_save(round_offset + r + 1,
                                        {"box": box, "accum": acc})
        return box, acc

    def run_program(self, prog: RoundProgram, box: Mailbox,
                    accum: Optional[CostAccum] = None
                    ) -> Tuple[Mailbox, CostAccum]:
        return self.run_rounds(prog.fn, box, prog.n_rounds,
                               capacity=prog.capacity, accum=accum,
                               n_nodes=prog.n_nodes)

    def run_stages(self, stages, box: Mailbox,
                   accum: Optional[CostAccum] = None,
                   checkpointer=None, round_offset: int = 0
                   ) -> Tuple[Mailbox, CostAccum]:
        """Drive a heterogeneous round schedule: ``stages`` is a sequence of
        ``(round_fn, capacity)`` pairs, ``(round_fn, capacity, n_nodes)``
        triples or ``(round_fn, capacity, n_nodes, early_dests)``
        quadruples, each executed as one round.

        This is the staged counterpart of :meth:`run_program` for
        computations whose mailbox footprint changes per round (e.g. the
        d-ary hull merge tree, where each level concentrates up to ``a``
        partial results at one node — and the live node count shrinks by
        ``a`` per level).  Capacities and node counts are Python ints, so
        the schedule is static and the whole driver stays jit-compatible
        on array backends.  The optional ``early_dests`` flag declares
        overlap legality per round (see :meth:`run_rounds`); this base
        loop ignores it — :class:`ShardedEngine` overrides the driver to
        double-buffer maximal runs of consecutive early rounds."""
        acc = accum if accum is not None else CostAccum.zero()
        for r, stage in enumerate(stages):
            fn, cap = stage[0], stage[1]
            V = stage[2] if len(stage) > 2 else None
            box, stats = self.run_round(fn, box, r, capacity=cap, n_nodes=V)
            acc = acc.add_round_stats(stats)
            if checkpointer is not None:
                checkpointer.maybe_save(round_offset + r + 1,
                                        {"box": box, "accum": acc})
        return box, acc

    # -- host-side validity check -------------------------------------------
    def require_no_drops(self, accum: CostAccum, what: str = "program") -> None:
        """Host boundary: raise if any round overflowed mailbox capacity
        (the w.h.p. failure event of the paper's randomized algorithms)."""
        dropped = int(accum.dropped)
        if dropped:
            raise RuntimeError(
                f"{self.name} engine: {dropped} items exceeded mailbox "
                f"capacity while running {what}; raise the capacity or use "
                f"repro.core.queues for the Theorem 4.2 discipline")


# ---------------------------------------------------------------------------
# ReferenceEngine — numpy oracle
# ---------------------------------------------------------------------------

class ReferenceEngine(MREngine):
    """Per-item host-loop shuffle: the executable spec the array backends are
    tested against.  Slow on purpose; run it on small inputs."""

    name = "reference"

    def node_ids(self, n_nodes: int) -> np.ndarray:
        return np.arange(n_nodes, dtype=np.int32)

    def shuffle(self, dests, payload: Payload, n_nodes: int,
                capacity: int) -> Tuple[Mailbox, RoundStats]:
        dests = np.asarray(dests)
        flat_dest = dests.reshape(-1)
        n = flat_dest.shape[0]
        leaves, treedef = jax.tree_util.tree_flatten(payload)
        flat_leaves = [np.asarray(l).reshape((n,) + np.asarray(l).shape[dests.ndim:])
                       for l in leaves]
        out_leaves = [np.zeros((n_nodes, capacity) + fl.shape[1:], fl.dtype)
                      for fl in flat_leaves]
        valid = np.zeros((n_nodes, capacity), bool)
        recv_counts = np.zeros((n_nodes,), np.int64)
        dropped = 0
        for j in range(n):                       # FIFO: flattened source order
            d = int(flat_dest[j])
            if d < 0:
                continue
            r = int(recv_counts[d])
            recv_counts[d] += 1
            if r >= capacity:
                dropped += 1
                continue
            for fl, ol in zip(flat_leaves, out_leaves):
                ol[d, r] = fl[j]
            valid[d, r] = True
        if dests.ndim >= 2 and n:
            sent_per_node = np.sum(flat_dest.reshape(dests.shape[0], -1) >= 0,
                                   axis=1)
            max_sent = np.int32(sent_per_node.max(initial=0))
        else:
            # n == 0 with a (V, M) send shape: no source node sent anything.
            max_sent = np.int32(0 if dests.ndim >= 2 else 1)
        stats = RoundStats(
            items_sent=np.int32(np.sum(flat_dest >= 0)),
            max_sent=max_sent,
            max_received=np.int32(recv_counts.max(initial=0)),
            dropped=np.int32(dropped),
        )
        box = Mailbox(payload=jax.tree_util.tree_unflatten(treedef, out_leaves),
                      valid=valid)
        return box, stats


# ---------------------------------------------------------------------------
# LocalEngine — dense jnp mailboxes, scan-able round loops
# ---------------------------------------------------------------------------

class LocalEngine(MREngine):
    """Dense single-process backend on jnp arrays.  ``run_rounds`` rolls the
    loop into a ``lax.scan`` (round_idx arrives traced), so whole round
    programs jit-compile with no host syncs; pass ``use_scan=False`` for
    round functions that need a static Python round index.

    ``shuffle_impl`` selects the Shuffle hot loop (bit-identical semantics,
    pinned by the conformance suite):

    - ``"dense"`` (default): :func:`repro.core.mrmodel.shuffle` — one
      stable sort by destination, then a gather of each node's run;
    - ``"kernel"``: :func:`repro.core.kshuffle.kernel_shuffle` — the
      multi-tile radix Pallas composition, fused bincount_tiles →
      tile-local bitonic_sort (``interpret=True`` off TPU).
      ``get_engine("pallas")`` constructs this variant.

    The kernel path's guards (tile width vs node count, count-matrix
    budget — the old single-VMEM-tile and int32-keyspace cliffs are gone)
    are re-derived per shuffle call from that call's (n, V) shape
    (:func:`repro.core.kshuffle.kernel_fits`): a call whose shape exceeds
    them falls back to the bit-identical dense shuffle.  Every routing
    decision is counted in this engine's own ``route_log``
    (:class:`repro.core.kshuffle.RouteLog` — per-engine so concurrent
    services on different engines cannot interleave counts) and, when a
    tracer is attached, recorded as a ``shuffle.route`` trace event, so
    tests and benches can assert the kernel path was actually taken.
    Either route runs inside the ``mr.shuffle`` named scope.
    """

    name = "local"
    jittable = True
    vmappable = True

    def __init__(self, use_scan: bool = True, shuffle_impl: str = "dense",
                 tracer=None):
        super().__init__(tracer=tracer)
        if shuffle_impl not in ("dense", "kernel"):
            raise ValueError(f"shuffle_impl must be 'dense' or 'kernel', "
                             f"got {shuffle_impl!r}")
        self.use_scan = use_scan
        self.shuffle_impl = shuffle_impl
        from .kshuffle import RouteLog
        #: this engine's routing counters
        self.route_log = RouteLog()
        if shuffle_impl == "kernel":
            from .kshuffle import kernel_fits, kernel_shuffle
            self._kernel_fits = kernel_fits
            self._shuffle_fn = kernel_shuffle
            self.name = "pallas"
        else:
            self._shuffle_fn = _dense_shuffle

    def shuffle(self, dests, payload: Payload, n_nodes: int,
                capacity: int) -> Tuple[Mailbox, RoundStats]:
        dests = jnp.asarray(dests)
        fn = self._shuffle_fn
        if self.shuffle_impl == "kernel":
            n = int(np.prod(dests.shape))
            if self._kernel_fits(n, n_nodes):
                impl = "kernel"
                self.route_log.kernel += 1
            else:
                impl = "dense"
                self.route_log.dense += 1
                fn = _dense_shuffle      # per-stage guard: oversize -> dense
            tr = self.tracer
            if tr.enabled:
                # Recorded even at jax trace time: the decision fires once
                # per traced shape, exactly like the route_log counters.
                tr.trace_event("shuffle.route", impl=impl, n=n,
                               n_nodes=int(n_nodes), backend=self.name)
                tr.metrics.counter(f"shuffle.route.{impl}").inc()
        with jax.named_scope("mr.shuffle"):
            return fn(dests, payload, n_nodes, capacity)

    def run_rounds(self, f: RoundFn, box: Mailbox, n_rounds: int,
                   capacity: Optional[int] = None,
                   accum: Optional[CostAccum] = None,
                   n_nodes: Optional[int] = None,
                   checkpointer=None, round_offset: int = 0,
                   early_dests: bool = False
                   ) -> Tuple[Mailbox, CostAccum]:
        # early_dests is a Sharded scheduling hint; the scanned local loop
        # already overlaps nothing (one fused program), so it is ignored.
        acc = accum if accum is not None else CostAccum.zero()
        if not self.use_scan or n_rounds <= 1:
            return super().run_rounds(f, box, n_rounds, capacity, acc,
                                      n_nodes=n_nodes,
                                      checkpointer=checkpointer,
                                      round_offset=round_offset)
        cap = capacity if capacity is not None else box.capacity
        V = n_nodes if n_nodes is not None else box.n_nodes
        start = 0
        if cap != box.capacity or V != box.n_nodes:
            # Shape-uniform segmentation: the first round is a shape-change
            # round (it reshapes the mailbox to (V, cap)) and runs eagerly
            # traced; the remaining rounds are shape-uniform and roll into
            # one lax.scan — shrinking programs stay fully jitted.
            box, stats = self.run_round(f, box, 0, cap, n_nodes=V)
            acc = acc.add_round_stats(stats)
            start = 1
            if checkpointer is not None:
                checkpointer.maybe_save(round_offset + 1,
                                        {"box": box, "accum": acc})

        def step(carry, r):
            b, a = carry
            b2, stats = self.run_round(f, b, r, cap, n_nodes=V)
            return (b2, a.add_round_stats(stats)), None

        # A checkpointer segments the scan at checkpoint boundaries
        # (checkpoints are host-side I/O, invisible inside a trace); the
        # shape-uniform spans between boundaries still scan, so the
        # per-span compile caches across identical span lengths.
        span = (n_rounds - start if checkpointer is None
                else max(1, checkpointer.every))
        r = start
        while r < n_rounds:
            stop = min(n_rounds, r + span)
            if stop > r:
                (box, acc), _ = lax.scan(
                    step, (box, acc),
                    jnp.arange(r, stop, dtype=jnp.int32))
            if checkpointer is not None:
                checkpointer.maybe_save(round_offset + stop,
                                        {"box": box, "accum": acc})
            r = stop
        return box, acc


# ---------------------------------------------------------------------------
# ShardedEngine — the same semantics over a mesh axis
# ---------------------------------------------------------------------------

class ShardedEngine(MREngine):
    """Distributed backend: nodes are partitioned contiguously across a mesh
    axis (shard s owns nodes [s*V/n, (s+1)*V/n)) and the Shuffle step runs as
    a two-phase route, each phase its own jitted ``shard_map`` program
    (DESIGN.md §13):

      1. **hop** — a lossless keyed ``all_to_all``
         (:func:`repro.core.distributed.keyed_hop` with per-pair capacity =
         the shard's item count) delivers every item to its owner shard in
         source-shard order;
      2. **scatter** — the per-shard local shuffle (dense or Pallas kernel)
         places arrivals into the owner's (V_local, capacity) mailbox slots.

    Because sources are contiguous per shard and phase 1 preserves source
    order, the composition implements exactly the global FIFO + overflow
    semantics of :class:`LocalEngine` at any axis size; with axis size 1 it
    degenerates to the local operation (how the CPU tests validate it).

    Splitting the phases makes the hierarchical route explicit *and*
    schedulable: the per-shard scatter is no longer barriered inside the
    same XLA program as the inter-shard collective, so for stages declared
    ``early_dests`` (destinations depend only on node ids and the static
    schedule) the overridden :meth:`run_rounds` / :meth:`run_stages`
    double-buffer rounds — JAX's async dispatch keeps round r+1's hop in
    flight while round r's reducer compute and scatter execute, with the
    hop's receive buffers donated into the scatter (off CPU) so no copy
    lands between the phases.  The overlapped path defers all per-round
    stat folds to the end of the run (per-round host reads would drain the
    device queue to depth 1); results and per-round ``CostAccum`` are
    bit-identical to the sequential path because both run the *same two
    programs per round* in the same order — only the host's issue/sync
    schedule differs.  Construct with ``overlap=False`` for a
    strictly-sequential comparator (benches, A/B tests); a checkpointer
    also forces the sequential path, since round-boundary snapshots need
    every round's state materialized.

    Node counts and the leading dim of 1-D destination arrays must be
    divisible by the axis size — grow V with :meth:`aligned_nodes`.

    ``shuffle_impl`` selects the phase-2 per-shard local scatter: ``"dense"``
    (default, :func:`repro.core.mrmodel.shuffle`) or ``"kernel"`` (the Pallas
    :func:`repro.core.kshuffle.kernel_shuffle`) — the same choice
    :class:`LocalEngine` exposes, applied inside ``shard_map``.  The kernel
    guards are re-derived **per call** through the same
    :func:`repro.core.kshuffle.kernel_fits` predicate LocalEngine uses (not
    baked in at ``_build`` time), so in a shape-scheduled program the late
    shrinking levels route through the kernel scatter even when the entry
    level cannot, and every decision lands in this engine's own
    ``route_log``.

    The hop program is named ``mr_hop`` and its body runs in the ``mr.hop``
    named scope; the scatter program is ``mr_scatter``, in ``mr.shuffle``.
    So on a chip each program's name gives its layer.
    """

    name = "sharded"

    def __init__(self, axis_name: str = "nodes",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 shuffle_impl: str = "dense", tracer=None,
                 overlap: bool = True):
        super().__init__(tracer=tracer)
        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), (axis_name,),
                                 axis_types=(AxisType.Auto,))
        if axis_name not in mesh.axis_names:
            raise ValueError(f"axis {axis_name!r} not in mesh {mesh.axis_names}")
        if shuffle_impl not in ("dense", "kernel"):
            raise ValueError(f"shuffle_impl must be 'dense' or 'kernel', "
                             f"got {shuffle_impl!r}")
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.shuffle_impl = shuffle_impl
        #: double-buffer rounds of early_dests stages (False = always run
        #: the strictly-sequential per-round schedule — the comparator the
        #: parity tests measure against)
        self.overlap = overlap
        from .kshuffle import RouteLog
        self.route_log = RouteLog()          # this engine's routing counters
        if shuffle_impl == "kernel":
            from .kshuffle import kernel_fits, kernel_shuffle
            self._kernel_fits = kernel_fits
            self._local_shuffle = kernel_shuffle
        else:
            self._local_shuffle = _dense_shuffle

    def aligned_nodes(self, n_nodes: int) -> int:
        return -(-max(1, int(n_nodes)) // self.n_shards) * self.n_shards

    def _build_hop(self, n_nodes: int, lead: int, n_leaves: int):
        """Jit the phase-1 program: the keyed ``all_to_all`` hop plus the
        send-side global stats (items_sent, max_sent).  Independent of
        ``capacity`` and of the phase-2 scatter implementation, so one hop
        lowering is shared by every stage with the same send shape."""
        from .distributed import keyed_hop

        axis = self.axis_name

        @jax.named_scope("mr.hop")
        def mr_hop(dests, *leaves):
            flat_dest = dests.reshape(-1).astype(jnp.int32)
            n_local = flat_dest.shape[0]
            local_dest, recv_flat = keyed_hop(dests, leaves, axis, n_nodes)
            # Send-side global stats: identical on every shard after the
            # collectives.
            items_sent = lax.psum(jnp.sum(flat_dest >= 0), axis)
            if lead > 1 and n_local > 0:
                sent_per_node = jnp.sum(
                    (flat_dest >= 0).reshape(dests.shape[0], -1), axis=1)
                max_sent = lax.pmax(jnp.max(sent_per_node), axis)
            else:
                # Empty (V, M) sends have no source nodes: max_sent = 0,
                # matching the dense and reference backends.
                max_sent = jnp.array(0 if lead > 1 else 1, jnp.int32)
            return (local_dest, list(recv_flat),
                    items_sent.astype(jnp.int32),
                    jnp.asarray(max_sent, jnp.int32))

        P = jax.sharding.PartitionSpec
        in_specs = (P(axis),) + (P(axis),) * n_leaves
        out_specs = (P(axis), [P(axis)] * n_leaves, P(), P())
        return jax.jit(jax.shard_map(mr_hop, mesh=self.mesh,
                                     in_specs=in_specs, out_specs=out_specs))

    def _build_scatter(self, n_nodes: int, capacity: int, n_leaves: int,
                       use_kernel: bool):
        """Jit the phase-2 program: the per-shard local scatter (dense or
        Pallas kernel) of hop arrivals into (V_local, capacity) mailbox
        slots, plus the receive-side global stats.  Off CPU the hop's
        output buffers are donated in — they are dead after this call, so
        XLA may alias them instead of copying, and the scatter launches as
        its own program no longer barriered behind the collective."""
        axis = self.axis_name
        local_v = n_nodes // self.n_shards
        local_shuffle = self._local_shuffle if use_kernel else _dense_shuffle

        @jax.named_scope("mr.shuffle")
        def mr_scatter(local_dest, *recv_flat):
            box, st = local_shuffle(local_dest, list(recv_flat), local_v,
                                    capacity)
            return (box.payload, box.valid,
                    lax.pmax(st.max_received, axis),
                    lax.psum(st.dropped, axis))

        P = jax.sharding.PartitionSpec
        in_specs = (P(axis),) + (P(axis),) * n_leaves
        out_specs = ([P(axis)] * n_leaves, P(axis), P(), P())
        # pallas_call outputs carry no varying-axes annotation; the body's
        # outputs have explicit per-shard specs, so skipping the check is
        # sound.
        fn = jax.shard_map(mr_scatter, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=not use_kernel)
        donate = ()
        if self.mesh.devices.flat[0].platform != "cpu":
            # Donation is unimplemented on the CPU backend (warning spam);
            # elsewhere the hop outputs alias straight into the scatter.
            donate = tuple(range(1 + n_leaves))
        return jax.jit(fn, donate_argnums=donate)

    def shuffle(self, dests, payload: Payload, n_nodes: int,
                capacity: int) -> Tuple[Mailbox, RoundStats]:
        """The two-phase Shuffle: issue the hop program, then the scatter
        program, without ever blocking the host (async dispatch queues
        both)."""
        dests = jnp.asarray(dests)
        if n_nodes % self.n_shards:
            raise ValueError(
                f"n_nodes={n_nodes} must be divisible by axis size "
                f"{self.n_shards}; use aligned_nodes()")
        leaves, treedef = jax.tree_util.tree_flatten(payload)
        leaves = [jnp.asarray(l) for l in leaves]
        if dests.shape[0] % self.n_shards:
            if dests.ndim != 1:
                raise ValueError(
                    f"leading dim {dests.shape[0]} must be divisible by axis "
                    f"size {self.n_shards} for per-node sends")
            # 1-D entry shuffles: pad with "no item" — semantics unchanged.
            pad = self.n_shards - dests.shape[0] % self.n_shards
            dests = jnp.concatenate([dests, jnp.full((pad,), -1, dests.dtype)])
            leaves = [jnp.concatenate(
                [l, jnp.zeros((pad,) + l.shape[1:], l.dtype)]) for l in leaves]
        # Per-call kernel guard (same predicate LocalEngine routes through;
        # the phase-2 scatter sees n_shards * n_local = n_flat arrivals per
        # shard buffer).  Re-derived on every shuffle call — not baked in at
        # _build time — so late shrinking levels of shaped plans route
        # through the kernel scatter, and route_log sees each decision.
        use_kernel = False
        if self.shuffle_impl == "kernel":
            n = int(np.prod(dests.shape))
            use_kernel = self._kernel_fits(n, n_nodes // self.n_shards)
            if use_kernel:
                self.route_log.kernel += 1
            else:
                self.route_log.dense += 1
            tr = self.tracer
            if tr.enabled:
                tr.trace_event("shuffle.route",
                               impl="kernel" if use_kernel else "dense",
                               n=n, n_nodes=int(n_nodes), backend=self.name)
                tr.metrics.counter(
                    f"shuffle.route.{'kernel' if use_kernel else 'dense'}"
                ).inc()
        # Per-shape lowerings share the engine's bounded cache with compiled
        # plans (previously an unbounded private dict — DESIGN.md §8).  The
        # hop key carries no capacity and no scatter impl: one hop lowering
        # serves every stage with the same send shape.
        cache = self._ensure_cache()
        leaf_sig = tuple((l.shape, str(l.dtype)) for l in leaves)
        hop_key = ("hop", n_nodes, dests.shape, dests.ndim, leaf_sig)
        hop = cache.lookup(hop_key)
        if hop is None:
            hop = cache.store(hop_key, self._build_hop(
                n_nodes, dests.ndim, len(leaves)))
        local_dest, recv_flat, items_sent, max_sent = hop(dests, *leaves)
        recv_sig = tuple((l.shape, str(l.dtype)) for l in recv_flat)
        sc_key = ("scatter", n_nodes, capacity, local_dest.shape, recv_sig,
                  use_kernel)
        sc = cache.lookup(sc_key)
        if sc is None:
            sc = cache.store(sc_key, self._build_scatter(
                n_nodes, capacity, len(recv_flat), use_kernel))
        out_leaves, valid, max_received, dropped = sc(local_dest, *recv_flat)
        stats = RoundStats(items_sent=items_sent, max_sent=max_sent,
                           max_received=max_received, dropped=dropped)
        box = Mailbox(payload=jax.tree_util.tree_unflatten(treedef, out_leaves),
                      valid=valid)
        return box, stats

    # -- overlapped (double-buffered) round scheduling — DESIGN.md §13 -------
    def run_rounds(self, f: RoundFn, box: Mailbox, n_rounds: int,
                   capacity: Optional[int] = None,
                   accum: Optional[CostAccum] = None,
                   n_nodes: Optional[int] = None,
                   checkpointer=None, round_offset: int = 0,
                   early_dests: bool = False
                   ) -> Tuple[Mailbox, CostAccum]:
        if not (early_dests and self.overlap) or checkpointer is not None \
                or n_rounds <= 0:
            # Data-dependent destinations, a sequential comparator, or a
            # checkpointer (round-boundary snapshots materialize per-round
            # state) — the base per-round schedule.
            return super().run_rounds(f, box, n_rounds, capacity, accum,
                                      n_nodes=n_nodes,
                                      checkpointer=checkpointer,
                                      round_offset=round_offset)
        window = [(f, capacity, n_nodes, r) for r in range(n_rounds)]
        return self._run_overlapped(window, box, accum)

    def run_stages(self, stages, box: Mailbox,
                   accum: Optional[CostAccum] = None,
                   checkpointer=None, round_offset: int = 0
                   ) -> Tuple[Mailbox, CostAccum]:
        if checkpointer is not None or not self.overlap:
            return super().run_stages(stages, box, accum=accum,
                                      checkpointer=checkpointer,
                                      round_offset=round_offset)
        acc = accum if accum is not None else CostAccum.zero()
        stages = list(stages)
        i = 0
        while i < len(stages):
            if not (len(stages[i]) > 3 and stages[i][3]):
                fn, cap = stages[i][0], stages[i][1]
                V = stages[i][2] if len(stages[i]) > 2 else None
                box, stats = self.run_round(fn, box, i, capacity=cap,
                                            n_nodes=V)
                acc = acc.add_round_stats(stats)
                i += 1
                continue
            # Maximal run of consecutive early_dests rounds: one overlapped
            # window (each round keeps its global schedule index).
            window = []
            while i < len(stages) and len(stages[i]) > 3 and stages[i][3]:
                s = stages[i]
                window.append((s[0], s[1],
                               s[2] if len(s) > 2 else None, i))
                i += 1
            box, acc = self._run_overlapped(window, box, acc)
        return box, acc

    def _run_overlapped(self, window, box: Mailbox, accum
                        ) -> Tuple[Mailbox, CostAccum]:
        """Issue a window of ``(fn, capacity, n_nodes, round_idx)`` rounds
        without ever blocking the host between rounds.

        The double buffer is the device queue itself: because the host
        reads nothing back until the window ends, round r+1's hop program
        is dispatched while round r's scatter (and the reducer compute
        inside fn) is still executing — the all_to_all flies under the
        compute.  Per-round :class:`RoundStats` stay on device in issue
        order and fold into the accumulator at the end, so the resulting
        ``CostAccum`` is bit-identical to the sequential schedule (same
        values, same fold order).

        No span here reads a device value or blocks: each round runs in an
        ``engine.round`` profiler annotation, and with a live tracer each
        issued round records a ``pipeline.hop`` event inside one
        ``pipeline.overlap`` span over the host's issue of the window.
        The device time of the hop itself is read from a profiler trace
        (the ``mr_hop`` program)."""
        acc = accum if accum is not None else CostAccum.zero()
        tr = self.tracer
        pending = []
        self.route_log.overlapped += len(window)
        with tr.span("pipeline.overlap", rounds=len(window),
                     backend=self.name):
            for fn, capacity, n_nodes, r in window:
                cap = capacity if capacity is not None else box.capacity
                V = n_nodes if n_nodes is not None else box.n_nodes
                with annotate("engine.round", round=r):
                    with jax.named_scope("mr.round"):
                        dests, payload = fn(r, self.node_ids(box.n_nodes),
                                            box)
                    box, st = self.shuffle(dests, payload, V, cap)
                pending.append(st)
                if tr.enabled:
                    tr.event("pipeline.hop", round=int(r), n_nodes=int(V),
                             capacity=int(cap), backend=self.name)
                    tr.count("pipeline.hops")
        if tr.enabled:
            tr.count("pipeline.overlaps")
        for st in pending:
            acc = acc.add_round_stats(st)
        return box, acc


@functools.lru_cache(maxsize=1)
def default_engine() -> MREngine:
    """The engine algorithms fall back to when none is passed (a shared
    LocalEngine — cheap, jittable, single-process)."""
    return LocalEngine()


def get_engine(name: str, **kwargs) -> MREngine:
    """Engine factory.  Registered names:

    - ``"reference"`` — :class:`ReferenceEngine`, numpy per-item host loop
      (the executable spec; slow on purpose);
    - ``"local"`` — :class:`LocalEngine`, dense jnp shuffles, scan/jit round
      loops (the default substrate);
    - ``"pallas"`` — :class:`LocalEngine` with ``shuffle_impl="kernel"``:
      the shuffle hot loop runs the Pallas kernel composition
      (:func:`repro.core.kshuffle.kernel_shuffle`; ``interpret=True`` off
      TPU), everything else identical to ``"local"``;
    - ``"sharded"`` — :class:`ShardedEngine`, the same program over a mesh
      axis via ``shard_map`` + ``all_to_all``.

    >>> get_engine("local").name
    'local'
    >>> get_engine("pallas").shuffle_impl
    'kernel'
    """
    engines = {"reference": ReferenceEngine, "local": LocalEngine,
               "sharded": ShardedEngine,
               "pallas": functools.partial(LocalEngine, shuffle_impl="kernel")}
    if name not in engines:
        raise ValueError(f"unknown engine {name!r}; pick from {sorted(engines)}")
    return engines[name](**kwargs)
