"""2-D convex hull as a pure engine round program (paper §1.4 + §4.3).

Round structure (all shapes static, end-to-end jittable on LocalEngine and
runnable unchanged on Reference/Sharded):

  0. pivot stage — x-quantile splitters from a random sample (the §4.3
     pivot construction, shared with ``sample_sort_mr`` via
     :func:`repro.core.sortmr.quantile_splitters`), accounted as its
     O(log_M s) rounds;
  1. entry shuffle — every point routed to the reducer owning its x-bucket
     (disjoint x-ranges, <= M points each w.h.p.; overflow is the reported
     ``stats.dropped`` event);
  2. d-ary merge tree, one engine round per level: every active node
     lex-sorts its padded run, reduces it with the vectorized monotone
     chain (:mod:`.chain` — no host Python), and sends its partial hull to
     the leader of its a-block; height ceil(log_a V) with a = max(2, M/2),
     so O(log_M N) rounds total;
  3. finalize round — the root re-sorts, chains, and keeps the hull at
     itself in CCW order (FIFO slots preserve it).

Merge capacities grow as min(n, a^k * cap0) — the worst case when every
point is extreme — so the tree itself can never drop; only the randomized
bucket stage carries the w.h.p. failure event, exactly as in the paper.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..costmodel import CostAccum, MRCost, log_M, tree_height
from ..plan import Plan, account_stage, entry_stage, round_stage
from ..sortmr import default_oversample, pivot_sample_size, quantile_splitters
from .chain import hull_of_runs


class EngineHullResult(NamedTuple):
    """Jit-friendly hull output: fixed-shape padded vertices + count."""

    points: jnp.ndarray   # (cap, 2) float32; rows [count:] are zero padding
    count: jnp.ndarray    # scalar int32 — number of hull vertices
    stats: CostAccum      # valid iff stats.dropped == 0


def hull2d_plan(n: int, M: int, *, oversample: Optional[int] = None,
                slack: float = 3.0,
                n_nodes: Optional[int] = None, align=None,
                shape: bool = True) -> Plan:
    """2-D convex hull (CCW from the lexicographic minimum) as a plan
    builder — the module-docstring round structure as a static stage table:
    pivot-sort accounting, the x-bucket entry shuffle, one named stage per
    d-ary merge level (capacities growing as min(n, a^k * cap0) — the
    all-points-extreme worst case, so the tree itself can never drop), and
    the finalize round.  Input at execute time: ``(points,)`` of shape
    (n, 2); PRNG slot ``"splitters"`` drives the §4.3 pivot sample.

    ``shape=True`` (default) emits the *shape-scheduled* merge tree
    (DESIGN.md §9): level k runs in its own physical mailbox of
    V_k = ceil(V / a^k) compactly-numbered nodes, so the footprint shrinks
    geometrically with the live node set and the peak physical mailbox
    stays O(a * slack * n) slots instead of V * n.  ``shape=False`` keeps
    the frozen entry shape (V, cap_k) at every level.  The two variants
    are bit-identical — same outputs, same per-round RoundStats/CostAccum
    (only physical padding differs) — on every backend.

    ``n_nodes`` overrides the reducer count — pass it when comparing
    backends whose ``aligned_nodes`` granularities differ, so both run the
    identical round schedule and stats; ``align`` applies a backend's
    granularity to the default count.
    """
    n, M = int(n), int(M)
    if n == 0:
        return Plan(
            name="hull2d", fingerprint=("hull2d-trivial", 0), n_nodes=1,
            stages=(),
            prologue=lambda inputs, keys: {},
            epilogue=lambda st: EngineHullResult(
                points=jnp.zeros((0, 2), jnp.float32), count=jnp.int32(0),
                stats=st.accum),
            round_bound=0)      # no input_spec: any empty input is accepted
    M_eff = max(2, M)
    if n_nodes is not None:
        V = int(n_nodes)
    else:
        V = max(1, -(-n // M_eff))
        if align is not None:
            V = int(align(V))
    a = max(2, M_eff // 2)                       # merge-tree arity
    n_levels = tree_height(V, a) if V > 1 else 0
    if oversample is None:
        oversample = default_oversample(V, slack)
    s = pivot_sample_size(n, V, oversample)      # static, = runtime sample
    piv_rounds = max(1, log_M(max(s, 2), M_eff))
    cap0 = min(n, max(1, int(math.ceil(slack * n / V))))
    fingerprint = ("hull2d", n, M, V, oversample, float(slack), bool(shape))

    def prologue(inputs, keys):
        pts = jnp.asarray(inputs[0], jnp.float32)
        splitters, _ = quantile_splitters(pts[:, 0], V, oversample,
                                          keys["splitters"])
        return {"pts": pts, "splitters": splitters}

    def emit_entry(carry):
        pts = carry["pts"]
        bucket = jnp.clip(
            jnp.searchsorted(carry["splitters"], pts[:, 0], side="left"),
            0, V - 1).astype(jnp.int32)
        return bucket, pts

    def make_chain_and_send(block: int, compact: bool):
        # Every active node reduces its run with the monotone chain and
        # sends its partial hull to its a-block's leader.  Frozen numbering:
        # the leader keeps its original id (ids // block) * block; compact
        # (shape-scheduled) numbering: level k+1's node j' receives from
        # level k's nodes [j'*a, (j'+1)*a) — same groups, same stats, the
        # mailbox just has no dead rows.
        def make_fn(carry):
            def fn(r, ids, b):
                hulls, h = hull_of_runs(b.payload, b.valid)
                leader = ids // a if compact else (ids // block) * block
                slot = jnp.arange(hulls.shape[1], dtype=jnp.int32)
                dests = jnp.where(slot[None, :] < h[:, None],
                                  leader[:, None], -1)
                return dests, hulls
            return fn
        return make_fn

    def make_finalize(carry):
        def finalize(r, ids, b):
            hulls, h = hull_of_runs(b.payload, b.valid)
            slot = jnp.arange(hulls.shape[1], dtype=jnp.int32)
            dests = jnp.where(slot[None, :] < h[:, None], ids[:, None], -1)
            return dests, hulls
        return finalize

    stages = [account_stage("pivot-sort",
                            ((s, min(s, M_eff)),) * piv_rounds),
              entry_stage("entry", V, cap0, emit_entry)]
    cap = cap0
    v_level = V                                  # live nodes entering level k
    for k in range(n_levels):
        cap = min(n, a * cap)
        v_level = -(-v_level // a)               # live nodes after the merge
        # early_dests: merge-tree leaders are pure functions of node id and
        # the level's static block size — the a-ary tree double-buffers on
        # ShardedEngine.
        stages.append(round_stage(f"merge-{k}",
                                  make_chain_and_send(a ** (k + 1), shape), 1,
                                  capacity=cap,
                                  n_nodes=v_level if shape else None,
                                  early_dests=True))
    stages.append(round_stage("finalize", make_finalize, 1, capacity=cap,
                              n_nodes=v_level if shape else None,
                              early_dests=True))

    def epilogue(state):
        box = state.box
        count = jnp.sum(box.valid[0]).astype(jnp.int32)
        return EngineHullResult(points=box.payload[0], count=count,
                                stats=state.accum)

    return Plan(name="hull2d", fingerprint=fingerprint, n_nodes=V,
                stages=tuple(stages), prologue=prologue, epilogue=epilogue,
                round_bound=piv_rounds + 1 + n_levels + 1,
                prng_slots=("splitters",), default_seed=7,
                input_spec=(((n, 2), None),))


def convex_hull_2d_mr(points: jnp.ndarray, M: int, *, engine=None,
                      key: Optional[jax.Array] = None,
                      n_nodes: Optional[int] = None,
                      slack: float = 3.0,
                      oversample: Optional[int] = None
                      ) -> EngineHullResult:
    """Deprecated wrapper over :func:`hull2d_plan`: builds the plan,
    compiles it on ``engine`` (cached per fingerprint) and runs it on
    ``points`` (n, 2).  Prefer the plan API (repro.core.api)."""
    from ..api import deprecated_entry
    deprecated_entry("convex_hull_2d_mr", "hull2d_plan")
    if engine is None:
        from ..engine import default_engine
        engine = default_engine()
    pts = jnp.asarray(points, jnp.float32)
    plan = hull2d_plan(pts.shape[0], M, oversample=oversample, slack=slack,
                       n_nodes=n_nodes, align=engine.aligned_nodes)
    return engine.compile(plan)(pts, key=key)


def convex_hull_2d(points, M: int, *, engine=None,
                   key: Optional[jax.Array] = None,
                   cost: Optional[MRCost] = None,
                   slack: float = 3.0) -> np.ndarray:
    """Host wrapper: trimmed (h, 2) float64 hull, CCW from the lex-min.

    Enforces the strict model (raises on mailbox overflow — raise ``slack``
    if the randomized bucket stage fires) and feeds the ``cost`` adapter.
    """
    if engine is None:
        from ..engine import default_engine
        engine = default_engine()
    pts = jnp.asarray(points, jnp.float32)
    plan = hull2d_plan(pts.shape[0], M, slack=slack,
                       align=engine.aligned_nodes)
    res = engine.compile(plan)(pts, key=key)
    engine.require_no_drops(res.stats, what="2-D convex hull")
    if cost is not None:
        cost.absorb(res.stats)
    h = int(res.count)
    return np.asarray(res.points, np.float64)[:h]


def hull_round_bound(n: int, M: int, oversample: Optional[int] = None,
                     n_nodes: Optional[int] = None) -> int:
    """Concrete ceiling for the engine hull's round count: pivot-sort rounds
    + entry shuffle + merge-tree height + finalize (the paper's O(log_M N)).

    The default reducer count matches ``convex_hull_2d_mr`` on backends
    whose ``aligned_nodes`` is the identity (Reference/Local, and Sharded
    at axis size 1).  A multi-shard ShardedEngine aligns V up, which can
    add a merge level — pass the engine's aligned count as ``n_nodes``
    (to both this bound and ``convex_hull_2d_mr``) when asserting there.
    """
    M_eff = max(2, int(M))
    V = int(n_nodes) if n_nodes is not None else max(1, -(-n // M_eff))
    if oversample is None:
        oversample = default_oversample(V, 3.0)   # hull2d_plan's slack
    s = pivot_sample_size(n, V, oversample)
    a = max(2, M_eff // 2)
    return (max(1, log_M(max(s, 2), M_eff)) + 1
            + (tree_height(V, a) if V > 1 else 0) + 1)
