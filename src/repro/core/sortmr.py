"""Sorting in the MapReduce model (paper §4.3 and Lemma 4.3 / Appendix A).

``brute_force_sort``: every pair of items is compared at a (tiled) node
v_{i,j}; summing each row of the comparison matrix with the Lemma 2.2
bottom-up phase yields each item's rank.  O(log_M N) rounds but O(N^2 log_M N)
communication — only viable for small inputs, which is exactly how §4.3 uses
it: on the Theta(sqrt(N)) pivots.

``sort_plan`` is the paper's §4.3 sample sort as a *plan builder* (DESIGN.md
§8): the static radix schedule — pivot-sort accounting, entry shuffle,
bucket-refinement rounds, reducer-local sort — is emitted as a declarative
:class:`~repro.core.plan.Plan` from (n, M) alone, compiled once per backend
through ``engine.compile(plan)`` and executed (or vmap-batched) on data.

The historical entry points survive as thin deprecated wrappers:
``sample_sort_mr`` builds+compiles+runs the plan; the seed's host-recursive
numpy ``sample_sort`` delegates to the same plan (escalating capacity until
the w.h.p. drop event clears) so the two sorters can no longer drift.

Recursion bottoms out in a per-reducer local sort: on TPU that is the bitonic
in-VMEM Pallas kernel (:mod:`repro.kernels.bitonic_sort`); here we call its
jnp oracle.

Optimized counterpart: single fused ``jax.lax.sort`` per shard + all_to_all
redistribution (see repro.core.distributed.sharded_sample_sort).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .costmodel import CostAccum, MRCost, log_M
from .multisearch import brute_force_multisearch, multisearch
from .plan import Plan, account_stage, entry_stage, round_stage


def brute_force_sort(x: jnp.ndarray, M: int,
                     cost: Optional[MRCost] = None) -> jnp.ndarray:
    """Lemma 4.3: rank by all-pairs comparison, then permute by rank.

    Stable: ties are broken by input index (the paper assumes an indexed
    collection; index = position)."""
    n = x.shape[0]
    # rank_i = |{j : x_j < x_i or (x_j == x_i and j < i)}| computed in tiles.
    tile = max(2, M)
    n_tiles = math.ceil(n / tile)
    idx = jnp.arange(n)
    ranks = jnp.zeros((n,), jnp.int32)
    for bi in range(n_tiles):
        sl = slice(bi * tile, min((bi + 1) * tile, n))
        xi, ii = x[sl], idx[sl]
        acc = jnp.zeros((xi.shape[0],), jnp.int32)
        for bj in range(n_tiles):
            sj = slice(bj * tile, min((bj + 1) * tile, n))
            xj, ij = x[sj], idx[sj]
            less = (xj[None, :] < xi[:, None])
            tie = (xj[None, :] == xi[:, None]) & (ij[None, :] < ii[:, None])
            acc = acc + jnp.sum(less | tie, axis=1, dtype=jnp.int32)
        ranks = ranks.at[sl].set(acc)
    out = jnp.zeros_like(x).at[ranks].set(x)
    if cost is not None:
        repl = max(1, log_M(max(n_tiles, 2), max(2, M)))
        for _ in range(repl):                       # replicate rows+cols
            cost.round(items_sent=2 * n * n_tiles, max_io=M)
        cost.round(items_sent=n * n_tiles, max_io=M)        # compare
        for _ in range(max(1, log_M(max(n_tiles, 2), max(2, M)))):
            cost.round(items_sent=n * n_tiles, max_io=M)    # row-sum tree
        cost.round(items_sent=n, max_io=1)                  # permute by rank
    return out


def sample_sort(x: jnp.ndarray, M: int, key: Optional[jax.Array] = None,
                cost: Optional[MRCost] = None,
                _depth: int = 0) -> jnp.ndarray:
    """Deprecated: the seed's host-recursive §4.3 sample sort.

    Delegates to the engine-native sort plan (:func:`sort_plan` on the
    default engine) so the two sorters cannot drift; the w.h.p. mailbox
    overflow event is handled the way the paper handles it — by retrying
    with more capacity (escalating ``slack``, finally collapsing to a
    single reducer, which always fits).  ``cost`` absorbs the plan's
    functional accounting.  ``_depth`` is accepted for back-compat and
    ignored (there is no host recursion anymore)."""
    from .api import deprecated_entry
    deprecated_entry("sample_sort", "sort_plan")
    res = sort_plan_escalating(jnp.asarray(x), M, key=key)
    if cost is not None:
        cost.absorb(res.stats)
    return res.values


def sort_plan_escalating(x: jnp.ndarray, M: int, *, key=None,
                         engine=None) -> "EngineSortResult":
    """Run the sort plan, retrying the w.h.p. drop event with more capacity
    the way the paper does: defaults -> generous slack -> one reducer
    (cap >= n, cannot drop).  Duplicate-heavy inputs no longer need the
    retries: the plan spreads keys that tie a splitter over every bucket
    their value spans, so such inputs succeed at the first rung.  The one
    escalate-until-no-drops policy — shared by the deprecated
    ``sample_sort`` and the data pipeline's paper shuffle.  Host-level
    (reads ``stats.dropped``): not for use under jit."""
    if engine is None:
        from .engine import default_engine
        engine = default_engine()
    x = jnp.asarray(x)
    n = x.shape[0]
    for slack, n_nodes in ((3.0, None), (8.0, None), (1.0, 1)):
        plan = sort_plan(n, M, dtype=x.dtype, slack=slack, n_nodes=n_nodes,
                         align=engine.aligned_nodes)
        res = engine.compile(plan)(x, key=key)
        if int(res.stats.dropped) == 0:
            break
    return res


class EngineSortResult(NamedTuple):
    """Output of the engine-driven sample sort."""

    values: jnp.ndarray          # (n,) ascending — valid iff stats.dropped == 0
    stats: CostAccum


def pivot_sample_size(n: int, n_buckets: int, oversample: int) -> int:
    """Static Theta(n_buckets * oversample) sample size of the §4.3 pivot
    stage — the single source of truth shared by :func:`quantile_splitters`
    (runtime) and the plans' pivot-sort accounting (``sort_plan``,
    ``hull2d_plan``), so declared schedules cannot drift from execution."""
    return int(min(n, max(2, n_buckets * oversample)))


#: Target probability that any bucket of a sample sort overflows.
_OVERFLOW_TARGET = 1e-9


def default_oversample(n_buckets: int, slack: float) -> int:
    """Pivot samples per bucket that make the w.h.p. overflow event
    negligible: at least 8, and enough that all ``n_buckets`` buckets stay
    within ``slack`` times their mean share with probability 1 - 1e-9.

    A bucket between sample quantiles k samples apart holds a share of
    about Gamma(k)/s; its Chernoff tail P(Gamma(k) > a k) <=
    exp(-k (a - 1 - ln a)) at a = slack, union-bounded over the buckets,
    gives k.  A fixed k = 8 overflows a 4096-bucket sort in about one run
    in five."""
    rate = slack - 1.0 - math.log(slack) if slack > 1 else 0.0
    if n_buckets <= 1 or rate <= 0:
        return 8
    need = (math.log(n_buckets) - math.log(_OVERFLOW_TARGET)) / rate
    return max(8, math.ceil(need))


def quantile_splitters(x: jnp.ndarray, n_buckets: int, oversample: int,
                       key: jax.Array) -> Tuple[jnp.ndarray, int]:
    """§4.3 pivot stage: the ``n_buckets - 1`` sample-quantile splitters of a
    Theta(n_buckets * oversample) random sample of ``x``.

    Returns (splitters ascending, sample size s).  Shared by the engine
    sample sort and the geometry round programs (the 2-D hull buckets points
    by x through the same splitter construction); ``s`` is what the caller
    accounts as the pivot-sort stage (O(log_M s) rounds moving s samples).
    Pure, jit-safe: shapes depend only on static (n, n_buckets, oversample).
    """
    n = x.shape[0]
    s = pivot_sample_size(n, n_buckets, oversample)
    sample = jnp.sort(x[jax.random.permutation(key, n)[:s]])
    # Static host indices: (n_buckets - 1) * s passes int32 at chip sizes.
    idx = np.arange(1, n_buckets, dtype=np.int64) * s // n_buckets
    return sample[idx], s


#: Most splitters the grouped lookup compares a key against (a lane width);
#: a wider branching B keeps the binary search.
GROUPED_LOOKUP_MAX = 128


def _order_keys(x: jnp.ndarray) -> jnp.ndarray:
    """Keys whose ``<`` is the total order ``jnp.sort`` and
    ``jnp.searchsorted`` use: a float becomes a signed integer after -0.0
    is made 0.0 and every NaN one NaN, which sorts last; an integer is its
    own key."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    x = jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)
    itype = jnp.dtype(f"int{8 * x.dtype.itemsize}")
    i = jax.lax.bitcast_convert_type(x, itype)
    return jnp.where(i < 0, i ^ jnp.iinfo(itype).max, i)


def splitter_runs(splitters) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(first, last): for each splitter index j, the first and the last
    index of the run of splitters equal to ``splitters[j]`` (equal in the
    order ``jnp.sort`` uses).  Keys equal to that value may go to any
    bucket in [first, last + 1].  O(V), no search."""
    s = _order_keys(jnp.asarray(splitters))
    m = s.shape[0]
    j = jnp.arange(m, dtype=jnp.int32)
    if m == 0:
        return j, j
    step = s[1:] != s[:-1]
    one = jnp.ones((1,), bool)
    first = jax.lax.cummax(jnp.where(jnp.concatenate([one, step]), j, 0))
    last = jax.lax.cummin(jnp.where(jnp.concatenate([step, one]), j, m - 1),
                          reverse=True)
    return first, last


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """A 32-bit integer hash (lowbias32): every output bit depends on every
    input bit."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _positions(shape, parent: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Where each item already is: its input index at the entry level
    (``parent`` None), else parent group * capacity + slot in the mailbox."""
    if parent is None:
        return jnp.arange(shape[0], dtype=jnp.int32)
    slot = jnp.arange(shape[1], dtype=jnp.int32)
    return parent[:, None] * shape[1] + slot[None, :]


def _tied_bucket(lo, hi, tie, parent, parent_width: int) -> jnp.ndarray:
    """Bucket ``lo``; where ``tie``, a bucket of [lo, hi] within the parent
    group (``parent`` None: the root), uniform by a hash of the key's
    position (:func:`_positions`)."""
    with jax.named_scope("sort.lookup.ties"):
        p_lo = 0 if parent is None else (parent * parent_width)[:, None]
        first = jnp.maximum(lo, p_lo)
        span = jnp.minimum(hi, p_lo + parent_width - 1) - first + 1
        pos = _positions(lo.shape, parent)
        u = (_mix32(pos) >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
        off = (u * span.astype(jnp.float32)).astype(jnp.int32)
        return jnp.where(tie, first + jnp.minimum(off, span - 1), lo)


@functools.partial(jax.jit, static_argnames=("B", "width", "parent_width",
                                              "compact"))
def grouped_lookup(splitters, first, last, vals, valid, ids, *, B: int,
                   width: int, parent_width: int,
                   compact: bool) -> jnp.ndarray:
    """Destinations of one sort level by its parent group's B-1 splitters.

    Value v may go to any bucket in [#(splitters < v), #(splitters <= v)]:
    those buckets concatenate to the sorted output whichever of them each
    copy takes.  A key that equals no splitter has one such bucket, and
    its group is that of ``searchsorted(splitters, v)``.  A key that equals
    a splitter takes a bucket of its range uniformly, by a hash of where
    it already is (:func:`_positions`), so a value with more copies than a
    bucket holds is spread over every bucket its value spans.

    Parent group p holds buckets [p*parent_width, (p+1)*parent_width); its
    boundary k in 0..B is splitter index (p*B + k)*width - 1 (0 and B are
    p's own edges; an index outside the splitters reads as a value of its
    own past every key).  Two prefix sums over the B-1 child boundaries,
    each one compare a boundary, give ``lo``: the start (``first``, from
    :func:`splitter_runs`) of the run of the first boundary not below v,
    and ``hi``: the end (``last``) of the run of the last boundary not
    above v.  ``lo <= hi`` iff v equals a child boundary; then v's range
    within p is [max(lo, p's first bucket), min(hi + 1, p's last)].
    Otherwise bucket ``lo`` lies in v's child group, which is
    ``lo // width``.

    ``vals`` is ``(n,)`` at the entry level (``ids`` None: one root group)
    or ``(rows, cap)`` in a mailbox whose row ``ids`` are the parent groups,
    compactly numbered (``compact``) or frozen at their leader node
    ``g * parent_width``.  The destination is the group, or its leader
    ``group * width`` when frozen; -1 where ``valid`` is False."""
    keys = _order_keys(jnp.asarray(vals))
    spl = _order_keys(jnp.asarray(splitters))
    m = spl.shape[0]
    spl = jnp.concatenate([spl, jnp.full((1,), jnp.iinfo(spl.dtype).max,
                                         spl.dtype)])
    if ids is None:
        parent = jnp.zeros((1,), jnp.int32)
    else:
        parent = jnp.asarray(ids, jnp.int32)
        if not compact:
            parent = parent // parent_width
    col = (-1,) + (1,) * (keys.ndim - 1)
    k = jnp.arange(B + 1, dtype=jnp.int32)
    j = (parent[:, None] * B + k) * width - 1          # (rows, B+1)
    real = (j >= 0) & (j < m)
    at = jnp.clip(j, 0, m)
    pad = jnp.full((1,), m, jnp.int32)
    run_lo = jnp.where(real, jnp.concatenate([first, pad])[at], j)
    run_hi = jnp.where(real, jnp.concatenate([last, pad])[at], j)
    table = spl[at[:, 1:B]]                            # (rows, B-1)
    step_lo = run_lo[:, 2:] - run_lo[:, 1:B]
    step_hi = jnp.where(real[:, 1:B], run_hi[:, 1:B] - run_hi[:, :B - 1], 0)
    lo = jnp.broadcast_to(run_lo[:, 1].reshape(col), keys.shape)
    hi = jnp.broadcast_to(run_hi[:, 0].reshape(col), keys.shape)
    for c in range(B - 1):                             # no (..., B-1) array
        t = table[:, c].reshape(col)
        lo = lo + jnp.where(t < keys, step_lo[:, c].reshape(col), 0)
        hi = hi + jnp.where(t <= keys, step_hi[:, c].reshape(col), 0)
    bucket = _tied_bucket(lo, hi + 1, lo <= hi,
                          None if ids is None else parent, parent_width)
    group = bucket // width
    dest = group if compact else group * width
    if valid is not None:
        dest = jnp.where(valid, dest, -1)
    # One pass in the mailbox's own layout: fused into the shuffle's
    # flattening, the compiler would relayout each broadcast splitter
    # column to the flat shape, and repeat the compares in every consumer.
    return jax.lax.optimization_barrier(dest)


def sort_plan(n: int, M: int, *, dtype=jnp.float32, levels: int = 1,
              oversample: Optional[int] = None, slack: float = 3.0,
              n_nodes: Optional[int] = None, align=None,
              shape: bool = True) -> Plan:
    """§4.3 sample sort as a plan builder (DESIGN.md §3 and §8).

    The recursion is flattened into a static radix schedule of ``levels``
    bucket-refinement rounds: with V reducers and branching
    B = V^(1/levels), round d routes every item to the leader of its
    B^(levels-1-d)-wide bucket group, so items converge to their final
    bucket in ``levels`` shuffles; one reducer-local sort round (the "keep"
    primitive) then orders each bucket.  Splitters are the V-1 sample
    quantiles of a Theta(V * oversample) random sample — the paper's pivot
    stage, accounted as its O(log_M) rounds.  ``oversample`` defaults to
    :func:`default_oversample` of (V, slack).

    The bucket lookup of each level is grouped (:func:`grouped_lookup`):
    a key in mailbox row p already lies in level d-1 group p, so it is
    compared with that group's B-1 child splitters only — one pass of
    B-1 compares, not a binary search over all V-1 splitters — with the
    entry level's single root group as p = 0.  That holds while B - 1 <=
    ``GROUPED_LOOKUP_MAX`` (128, one lane width); a wider plan (only
    ``levels=1`` with V > 129 in practice) keeps ``jnp.searchsorted``.
    Either path gives the same destinations, and its scope
    (``sort.lookup.grouped`` / ``sort.lookup.search``, inside
    ``sort.lookup``) names it in the compiled program.

    A key that equals a splitter may lie in any bucket from
    #(splitters < v) to #(splitters <= v); each level sends it to a
    bucket of that range within its parent group, uniformly by a hash of
    its input index (entry level) or of its mailbox row and slot, with
    no payload added (scope ``sort.lookup.ties``).  So a value with more
    copies than a bucket holds is spread over the buckets its value
    spans, and a key that equals no splitter goes where the binary
    search sends it.

    Everything here is static — shapes, capacities, the stage table — so
    the plan is built **without touching data**; inputs ``(x,)`` arrive at
    execute time.  ``align`` (e.g. ``engine.aligned_nodes``) rounds the
    default reducer count to a backend's layout granularity.  The executed
    result is valid iff ``stats.dropped == 0``: the paper's w.h.p. event,
    a bucket of the sample's quantiles past ``slack`` times its share,
    skewed and duplicated keys included.

    ``shape=True`` (default) shape-schedules the merge ladder (DESIGN.md
    §9): refinement level d runs in a physical mailbox of
    V_d = min(V, B^(d+1)) compactly-numbered group nodes (one per live
    bucket group) instead of the frozen V — so every level's footprint is
    ~slack*n slots rather than V * group_cap(0).  With ``levels=1`` there
    is no ladder and the two variants coincide; they are bit-identical
    (outputs and per-round stats) in all cases.
    """
    n, M = int(n), int(M)
    dtype = jnp.dtype(dtype)
    if n <= 1:
        return Plan(
            name="sort", fingerprint=("sort-trivial", n, str(dtype)),
            n_nodes=1, stages=(),
            prologue=lambda inputs, keys: {"x": jnp.asarray(inputs[0])},
            epilogue=lambda st: EngineSortResult(values=st.carry["x"],
                                                 stats=st.accum),
            round_bound=0, input_spec=(((n,), dtype),))
    levels = max(1, int(levels))
    M_eff = max(2, M)
    if n_nodes is not None:
        V = int(n_nodes)
    else:
        V = max(1, -(-n // M_eff))
        if align is not None:
            V = int(align(V))
    B = max(2, math.ceil(V ** (1.0 / levels))) if V > 1 else 1
    if oversample is None:
        oversample = default_oversample(V, slack)
    s = pivot_sample_size(n, V, oversample)       # static, = runtime sample
    piv_rounds = max(1, log_M(max(s, 2), M_eff))
    fingerprint = ("sort", n, M, str(dtype), levels, oversample,
                   float(slack), V, bool(shape))

    def group_nodes(d):
        return min(V, B ** (d + 1))

    def group_cap(d):
        return max(1, int(math.ceil(slack * n / group_nodes(d))))

    grouped = B - 1 <= GROUPED_LOOKUP_MAX

    def level_dest(c, vals, valid, ids, d):
        # Frozen numbering sends bucket group g to its leader node
        # g * width; the shape-scheduled ladder numbers level d's
        # min(V, B^(d+1)) live groups compactly (node g = group g) so the
        # mailbox carries no dead rows.  Same grouping either way — the
        # per-round stats are identical.
        width = B ** (levels - 1 - d)
        with jax.named_scope("sort.lookup"):
            if grouped:
                with jax.named_scope("sort.lookup.grouped"):
                    return grouped_lookup(c["splitters"], c["first"],
                                          c["last"], vals, valid, ids, B=B,
                                          width=width,
                                          parent_width=width * B,
                                          compact=shape)
            with jax.named_scope("sort.lookup.search"):
                lo = jnp.searchsorted(c["splitters"], vals, side="left")
                hi = jnp.searchsorted(c["splitters"], vals, side="right")
                parent = None
                if ids is not None:
                    parent = jnp.asarray(ids, jnp.int32) // (
                        1 if shape else width * B)
                bucket = _tied_bucket(lo, hi, hi > lo, parent, width * B)
        group = jnp.clip(bucket, 0, V - 1).astype(jnp.int32) // width
        dest = group if shape else group * width
        return dest if valid is None else jnp.where(valid, dest, -1)

    def prologue(inputs, keys):
        x = jnp.asarray(inputs[0])
        splitters, _ = quantile_splitters(x, V, oversample, keys["splitters"])
        carry = {"x": x, "splitters": splitters}
        if grouped:
            carry["first"], carry["last"] = splitter_runs(splitters)
        return carry

    stages = [
        # pivot sort: O(log_M s) rounds moving the s samples
        account_stage("pivot-sort", ((s, min(s, M_eff)),) * piv_rounds),
        # level 0 routes straight from the input collection
        entry_stage("entry", group_nodes(0) if shape else V, group_cap(0),
                    lambda c: (level_dest(c, c["x"], None, None, 0),
                               c["x"])),
    ]
    for d in range(1, levels):
        def make_refine(carry, _d=d):
            def refine(r, ids, b):
                return (level_dest(carry, b.payload, b.valid, ids, _d),
                        b.payload)
            return refine
        # early_dests: the refine ladder's group targets come from the
        # static level schedule (splitters are carry, not mailbox data) —
        # legal for the ShardedEngine double-buffered schedule.
        stages.append(round_stage(f"refine-{d}", make_refine, 1,
                                  capacity=group_cap(d),
                                  n_nodes=group_nodes(d) if shape else None,
                                  early_dests=True))

    big = (jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating)
           else jnp.iinfo(dtype).max)

    def make_local_sort(carry):
        # Reducer-local sort round: sort within the mailbox, keep at self.
        def local_sort(r, ids, b):
            svals = jnp.sort(jnp.where(b.valid, b.payload, big), axis=1)
            count = jnp.sum(b.valid, axis=1, keepdims=True)
            slot = jnp.arange(svals.shape[1], dtype=jnp.int32)[None, :]
            dest = jnp.where(slot < count, ids[:, None], -1)
            return dest, svals
        return local_sort

    stages.append(round_stage("local-sort", make_local_sort, 1,
                              early_dests=True))   # keep-at-self dests
    stages.append(account_stage("output", ((n, 1),)))   # leaves -> output

    def epilogue(state):
        # Output assembly: bucket-major compaction.  Valid slots are a FIFO
        # prefix per node, so output position i is slot i - offset of the
        # bucket whose run of positions holds i: a gather, not a scatter.
        box = state.box
        valid = jnp.asarray(box.valid)
        payload = jnp.asarray(box.payload)
        counts = jnp.sum(valid, axis=1, dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        pos = jnp.arange(n, dtype=jnp.int32)
        bucket = jnp.minimum(jnp.searchsorted(ends, pos, side="right"),
                             valid.shape[0] - 1)
        slot = jnp.minimum(pos - (ends[bucket] - counts[bucket]),
                           valid.shape[1] - 1)
        out = jnp.where(pos < ends[-1], payload[bucket, slot],
                        jnp.zeros((), dtype))
        return EngineSortResult(values=out, stats=state.accum)

    return Plan(name="sort", fingerprint=fingerprint, n_nodes=V,
                stages=tuple(stages), prologue=prologue, epilogue=epilogue,
                round_bound=piv_rounds + levels + 2,
                prng_slots=("splitters",), default_seed=7,
                input_spec=(((n,), dtype),))


def sample_sort_mr(x: jnp.ndarray, M: int, *, engine=None,
                   key: Optional[jax.Array] = None,
                   n_nodes: Optional[int] = None,
                   levels: int = 1, oversample: Optional[int] = None,
                   slack: float = 3.0) -> EngineSortResult:
    """Deprecated wrapper over :func:`sort_plan`: builds the plan, compiles
    it on ``engine`` (cached per fingerprint) and runs it on ``x``.  Prefer
    the plan API, which separates the static schedule from the data and
    exposes batching (``engine.compile(plan).batch(B)``)."""
    from .api import deprecated_entry
    deprecated_entry("sample_sort_mr", "sort_plan")
    if engine is None:
        from .engine import default_engine
        engine = default_engine()
    x = jnp.asarray(x)
    plan = sort_plan(x.shape[0], M, dtype=x.dtype, levels=levels,
                     oversample=oversample, slack=slack, n_nodes=n_nodes,
                     align=engine.aligned_nodes)
    return engine.compile(plan)(x, key=key)


def sort_opt(x: jnp.ndarray) -> jnp.ndarray:
    """Optimized counterpart: XLA's fused on-device sort."""
    return jnp.sort(x)


def sort_cost_bound(n: int, M: int) -> Tuple[int, int]:
    """Paper bound for sample sort: O(log_M N) rounds, O(N log_M N) words,
    as concrete ceilings (constants derived in EXPERIMENTS.md §Paper-validation):
    rounds <= c_r * log_M(n)^2 ... we use the measured-vs-asymptote check
    instead; this returns (log_M n, n * log_M n) as the unit scale."""
    return log_M(n, M), n * log_M(n, M)
