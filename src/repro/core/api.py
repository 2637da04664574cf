"""The compiled query API over MREngine: compile/execute/batch + plan cache.

This is the serving-facing half of the plan/compile/execute split
(DESIGN.md §8).  A :class:`~repro.core.plan.Plan` (built once from static
parameters by the ``*_plan`` builders re-exported below) is lowered by
``MREngine.compile(plan)`` into an :class:`Executable`:

- ``exe(*inputs, key=...)`` runs one query — on jit-capable backends the
  whole round program is a single ``jax.jit``-compiled callable, traced
  once per (plan fingerprint, input shapes/dtypes) and reused across calls;
- ``exe.batch(B)`` vmaps the *entire* round program, so B independent
  queries (B sorts, B multisearch DAGs, B hulls) execute in one device
  program — the batched-serving primitive of ROADMAP.md.  Backends that
  cannot vmap (the numpy ReferenceEngine, ShardedEngine) fall back to a
  loop with bit-identical outputs;
- compiled executables live in a **bounded per-engine plan cache**
  (:class:`BoundedCache`, the generalization of the private
  ``ShardedEngine._compiled`` dict) with LRU eviction and hit/miss
  counters surfaced through ``engine.cache_info()``.

Typical use::

    from repro.core import LocalEngine
    from repro.core.api import sort_plan

    engine = LocalEngine()
    plan = sort_plan(n=4096, M=64)            # static schedule, no data
    exe = engine.compile(plan)                # cached per fingerprint
    out = exe(x, key=key)                     # one jitted query
    outs = exe.batch(64)(xs, keys=keys)       # 64 queries, one program
"""
from __future__ import annotations

import warnings
from collections import OrderedDict
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .plan import Plan, execute_plan
from ..obs import NULL_TRACER
from ..obs.trace import annotate


class CacheInfo(NamedTuple):
    """Counters of a :class:`BoundedCache` (``engine.cache_info()``)."""

    hits: int
    misses: int
    evictions: int
    currsize: int
    maxsize: int


class BoundedCache:
    """LRU-bounded mapping with hit/miss/eviction counters.

    One instance per engine holds both compiled plan executables (keys
    ``("plan", fingerprint)``) and ShardedEngine's per-shape shuffle
    lowerings (keys ``("shuffle", ...)``) — the generalization of the
    previously unbounded ``ShardedEngine._compiled`` dict.
    """

    def __init__(self, maxsize: int = 128):
        self.maxsize = int(maxsize)
        self._data: "OrderedDict[Any, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def lookup(self, key):
        """Return the cached value or None; counts a hit or a miss."""
        try:
            value = self._data[key]
        except KeyError:
            self.misses += 1
            return None
        self.hits += 1
        self._data.move_to_end(key)
        return value

    def store(self, key, value):
        """Insert (evicting the least-recently-used entry when full) and
        return ``value``."""
        if key in self._data:
            self._data[key] = value
            self._data.move_to_end(key)
            return value
        while len(self._data) >= self.maxsize:
            self._data.popitem(last=False)
            self.evictions += 1
        self._data[key] = value
        return value

    def info(self) -> CacheInfo:
        return CacheInfo(hits=self.hits, misses=self.misses,
                         evictions=self.evictions, currsize=len(self._data),
                         maxsize=self.maxsize)

    def keys(self) -> tuple:
        """Snapshot of the cached keys, LRU-first.  Read-only introspection:
        unlike :meth:`lookup` it perturbs neither the recency order nor the
        hit/miss counters — what a serving layer's admission control needs
        to ask "would compiling this plan evict live work?" without lying
        to the eviction policy."""
        return tuple(self._data.keys())

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data


class Executable:
    """A Plan lowered onto one engine (obtain via ``engine.compile(plan)``).

    On jit-capable backends (LocalEngine, its Pallas variant) the round
    program is wrapped in a single ``jax.jit``; ``trace_count`` counts how
    many times it was actually (re)traced, so tests can assert the
    compile-once contract.  ReferenceEngine and ShardedEngine execute
    eagerly (the latter jits per-shape inside its shuffle, through the same
    bounded cache).
    """

    #: distinct batch sizes whose lowered callables are retained per
    #: executable (LRU) — each is a full vmapped round program, so this is
    #: bounded for the same reason the plan cache is
    batch_cache_size = 8

    def __init__(self, plan: Plan, engine):
        self.plan = plan
        self.engine = engine
        self._traces = 0
        self._batched = BoundedCache(self.batch_cache_size)

        def run(key, *inputs):
            self._traces += 1      # host side effect: fires once per trace
            return execute_plan(plan, engine, inputs, key=key)

        self._run = run
        self._fn = jax.jit(run) if getattr(engine, "jittable", False) else run

    @property
    def trace_count(self) -> int:
        """Number of lowerings of the round program.  On jit backends this
        stays flat across repeated same-shape calls (the compile-once
        contract); on eager backends it counts calls."""
        return self._traces

    def __call__(self, *inputs, key=None):
        tr = getattr(self.engine, "tracer", NULL_TRACER)
        if not tr.enabled:
            with annotate("exe.call"):
                return self._fn(key, *inputs)
        t0 = tr.clock()
        n0 = self._traces
        with annotate("exe.call"):
            out = self._fn(key, *inputs)
        backend = getattr(self.engine, "name", "?")
        if self._traces > n0 and getattr(self.engine, "jittable", False):
            tr.event("exe.compile", plan=self.plan.name, backend=backend,
                     trace_count=self._traces)
        tr.event("exe.call", _dur=tr.clock() - t0, plan=self.plan.name,
                 backend=backend)
        tr.count("exe.calls")
        return out

    # -- batching ------------------------------------------------------------
    def _batch_keys(self, keys, B: int):
        if keys is None:
            if self.plan.prng_slots:
                keys = jax.random.split(
                    jax.random.PRNGKey(self.plan.default_seed), B)
            else:
                keys = jnp.zeros((B, 2), jnp.uint32)
        keys = jnp.asarray(keys)
        if keys.shape[0] != B:
            raise ValueError(f"expected {B} keys, got {keys.shape[0]}")
        return keys

    def batch(self, n_queries: int) -> Callable:
        """Return a callable running ``n_queries`` independent queries.

        Inputs must be stacked along a new leading axis of size B;
        ``keys`` is an optional (B, 2) stack of PRNG keys (defaults to
        ``split(PRNGKey(default_seed), B)``).  On vmap-capable backends the
        whole round program is vmapped and jitted into **one device
        program**; otherwise a loop over the single-query executable
        produces bit-identical stacked outputs.
        """
        B = int(n_queries)
        cached = self._batched.lookup(B)
        if cached is not None:
            return cached
        if (getattr(self.engine, "jittable", False)
                and getattr(self.engine, "vmappable", False)):
            vfn = jax.jit(jax.vmap(self._run))

            def call(*inputs, keys=None):
                return vfn(self._batch_keys(keys, B), *inputs)
        else:
            def call(*inputs, keys=None):
                ks = self._batch_keys(keys, B)
                outs = [self._fn(ks[i],
                                 *jax.tree_util.tree_map(lambda a: a[i],
                                                         tuple(inputs)))
                        for i in range(B)]
                return jax.tree_util.tree_map(
                    lambda *leaves: jnp.stack(leaves), *outs)
        return self._batched.store(B, call)


def pad_batch(inputs: tuple, n_queries: int, keys=None):
    """Pad ``k`` stacked queries up to a fixed batch of ``n_queries``.

    The serving path runs every coalesced batch through one
    ``Executable.batch(B)`` callable at a **fixed** B: lowering a separate
    program per occupancy k would retrace on every partial batch (each
    distinct k is a distinct vmap lowering).  This helper makes the pad
    explicit: each leaf of ``inputs`` (stacked on a leading axis of size
    ``k``, with ``1 <= k <= B``) is padded to B rows by replicating its
    last row — real, in-distribution data, so the padded tail can never
    poison vmapped lanes with NaNs — and ``keys`` (a (k, 2) stack of PRNG
    keys, optional) is padded the same way.

    Returns ``(padded_inputs, padded_keys, valid)`` where ``valid`` is the
    boolean numpy mask of the k live rows: callers slice every output leaf
    with it (equivalently ``leaf[:k]``) to demultiplex, which restores
    bit-identity with k sequential single-query calls — vmapped lanes are
    independent, so the pad rows cannot perturb the live ones.
    ``padded_keys`` is None when ``keys`` is None.

    Padding runs on the **host** (numpy) by design: it sits on the serving
    hot path, where per-leaf device concats would each be their own tiny
    dispatch (and, per new shape, their own compile).  The padded arrays
    enter the device once, inside the jitted ``batch(B)`` call.
    """
    import numpy as np
    B = int(n_queries)
    leaves = jax.tree_util.tree_leaves(tuple(inputs))
    if not leaves:
        raise ValueError("pad_batch: empty inputs")
    k = int(np.shape(leaves[0])[0])
    if k < 1:
        raise ValueError("pad_batch: nothing to pad (k == 0)")
    if k > B:
        raise ValueError(f"pad_batch: {k} queries exceed the batch bound "
                         f"B={B}")

    def pad(leaf):
        leaf = np.asarray(leaf)
        if leaf.shape[0] != k:
            raise ValueError(
                f"pad_batch: inconsistent leading axis "
                f"{leaf.shape[0]} != {k}")
        if k == B:
            return leaf
        tail = np.broadcast_to(leaf[-1:], (B - k,) + leaf.shape[1:])
        return np.concatenate([leaf, tail], axis=0)

    padded = jax.tree_util.tree_map(pad, tuple(inputs))
    padded_keys = None if keys is None else pad(keys)
    valid = np.arange(B) < k
    return padded, padded_keys, valid


def compile_plan(plan: Plan, engine=None) -> Executable:
    """Module-level convenience for ``engine.compile(plan)`` (default
    engine = the shared LocalEngine)."""
    if engine is None:
        from .engine import default_engine
        engine = default_engine()
    return engine.compile(plan)


def deprecated_entry(old: str, new: str) -> None:
    """One-liner the legacy ``fn(x, M, engine=...)`` wrappers call: points
    at the plan builder that replaces them (DESIGN.md §8)."""
    warnings.warn(
        f"{old} is deprecated: build a plan with {new} and run it via "
        f"engine.compile(plan) — see repro.core.api (DESIGN.md §8)",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# The query surface: every algorithm's plan builder, one import away.
# ---------------------------------------------------------------------------
from .sortmr import sort_plan                                    # noqa: E402
from .multisearch import multisearch_plan                        # noqa: E402
from .prefix import prefix_plan, PrefixResult                    # noqa: E402
from .funnel import funnel_write_plan                            # noqa: E402
from .bsp import bsp_plan, BSPResult                             # noqa: E402
from .geometry.hull2d import hull2d_plan                         # noqa: E402
from .geometry.hull3d import hull3d_plan                         # noqa: E402
from .geometry.lp import lp_plan                                 # noqa: E402

__all__ = [
    "CacheInfo", "BoundedCache", "Executable", "compile_plan", "pad_batch",
    "sort_plan", "multisearch_plan", "prefix_plan", "PrefixResult",
    "funnel_write_plan", "bsp_plan", "BSPResult",
    "hull2d_plan", "hull3d_plan", "lp_plan",
]
