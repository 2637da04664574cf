"""Declarative round-program plans: the plan half of the plan/compile/execute
split (DESIGN.md §8).

The paper's headline bounds — O(log_M N) rounds for sorting (§4.3),
multi-searching (Thm 4.1) and the geometry applications (§1.4) — share one
structural property: once (N, M) are fixed, the *round schedule* is static;
only the data varies.  That is exactly the split JAX rewards, so this module
makes it an object: a :class:`Plan` is an algorithm with the data removed —

- **named stages** (:class:`PlanStage`), each declaring how many rounds it
  contributes and at what mailbox capacity, plus the callable that executes
  it against an :class:`~repro.core.engine.MREngine`;
- a **prologue** that turns the runtime inputs (and PRNG keys) into the
  initial carry, and an **epilogue** that turns the final
  :class:`PlanState` into the algorithm's result;
- the **paper round-bound ceiling** (``round_bound``) and the declared
  **PRNG slots** the plan consumes.

Plans are built by the ``*_plan`` builders in each algorithm module
(``sort_plan``, ``multisearch_plan``, ``hull2d_plan``, ...; re-exported from
:mod:`repro.core.api`) from *static* parameters only — shapes, M, dtypes —
never from data.  ``MREngine.compile(plan)`` lowers a plan once per
(fingerprint, backend) into a cached :class:`~repro.core.api.Executable`;
:func:`execute_plan` is the engine-agnostic interpreter both paths share.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .costmodel import CostAccum
from .mrmodel import Mailbox
from ..obs import NULL_TRACER, plan_token, round_event as _round_event
from ..obs.trace import annotate, not_tracing


class PlanStage(NamedTuple):
    """One named step of a plan's static schedule.

    ``rounds``, ``capacity`` and ``n_nodes`` are the *declared* schedule
    (what ``Plan.schedule()`` prints and ``Plan.total_rounds`` sums);
    ``apply`` is the executable body ``(engine, PlanState) -> PlanState``
    and must account exactly ``rounds`` rounds into the state's
    accumulator.  ``(n_nodes, capacity)`` is the stage's declared mailbox
    footprint ``(V_r, M_r)`` — the physical shape its shuffles target
    (Theorem 2.1 charges each round only its live communication, so
    shrinking programs declare shrinking footprints; DESIGN.md §9).
    ``capacity=None`` / ``n_nodes=None`` mean the stage inherits the
    current mailbox shape (or does not shuffle at all); backends apply
    their layout granularity via ``engine.aligned_nodes`` at execute
    time, so small late levels may collapse to one shard."""

    name: str
    rounds: int
    capacity: Optional[int]
    apply: Callable
    n_nodes: Optional[int] = None
    #: whether the stage physically shuffles (entry/round/custom stages) —
    #: accounting-only and compute stages set False so footprint metrics
    #: (peak/total_mailbox_slots) skip them even when both dims inherit
    shuffles: bool = True
    #: *declared* overlap legality (DESIGN.md §13): True promises the
    #: stage's destinations depend only on node ids and the static schedule
    #: (the sortmr refine ladder, hull2d merge tree, multisearch scan
    #: rounds), never on mailbox data — which lets ShardedEngine
    #: double-buffer its rounds (issue round r+1's all_to_all hop under
    #: round r's reducer compute).  Declared by the builder, never
    #: inferred; data-dependent CRCW/funnel writes stay False and always
    #: take the sequential schedule.  A scheduling hint only — results and
    #: CostAccum are bit-identical either way.
    early_dests: bool = False


class PlanState(NamedTuple):
    """Threaded execution state: the current mailbox (None before the entry
    shuffle), an arbitrary pytree ``carry`` (splitters, funnel frontiers,
    PRAM memory, ...) and the functional cost accumulator."""

    box: Optional[Mailbox]
    carry: Any
    accum: CostAccum


class Plan(NamedTuple):
    """A round program with the data removed (see module docstring).

    ``fingerprint`` is a hashable tuple of every static parameter that went
    into the build (name, n, M, dtypes, capacities, ...): two builder calls
    with equal static arguments yield equal fingerprints, which is what the
    engine plan cache keys on — closures are never compared."""

    name: str
    fingerprint: Tuple
    n_nodes: int
    stages: Tuple[PlanStage, ...]
    prologue: Callable            # (inputs: tuple, keys: dict) -> carry
    epilogue: Callable            # (PlanState) -> outputs
    round_bound: int              # concrete ceiling realizing the paper's O(.)
    prng_slots: Tuple[str, ...] = ()
    default_seed: int = 7
    #: per-input (shape, dtype-or-None) pairs (None entry/spec = unchecked);
    #: the plan bakes these statics in, so a mismatched runtime input would
    #: silently corrupt — execute_plan turns that into a ValueError.
    input_spec: Optional[Tuple] = None

    @property
    def total_rounds(self) -> int:
        """Rounds the declared schedule executes (must be <= round_bound)."""
        return sum(s.rounds for s in self.stages)

    def schedule(self) -> Tuple[Tuple[str, int, Optional[int],
                                      Optional[int]], ...]:
        """The static shape schedule as (stage name, rounds, capacity,
        n_nodes) rows — ``(n_nodes, capacity)`` is the declared per-stage
        mailbox footprint ``(V_r, M_r)``; None inherits."""
        return tuple((s.name, s.rounds, s.capacity, s.n_nodes)
                     for s in self.stages)

    @property
    def shape_fingerprint(self) -> Tuple:
        """The declared shape schedule as a hashable token; folded into the
        plan-cache key next to ``fingerprint`` so two plans that differ only
        in per-stage footprints never share a compiled executable."""
        return tuple((s.rounds, s.capacity, s.n_nodes) for s in self.stages)

    def _resolved_footprints(self):
        """(rounds, V_r, M_r) per *shuffling* stage with inherited dims
        resolved from the last declaring stage; accounting-only stages
        (``shuffles=False``) never touch a mailbox and are skipped — a
        shuffling stage that inherits both dims still counts at the
        inherited footprint (e.g. a frozen program's steady rounds)."""
        v, m = self.n_nodes, None
        rows = []
        for s in self.stages:
            v = s.n_nodes if s.n_nodes is not None else v
            m = s.capacity if s.capacity is not None else m
            if s.shuffles and v is not None and m is not None:
                rows.append((s.rounds, int(v), int(m)))
        return rows

    def peak_mailbox_slots(self) -> int:
        """Max declared physical footprint V_r * M_r over the schedule."""
        return max((v * m for _, v, m in self._resolved_footprints()),
                   default=0)

    def total_mailbox_slots(self) -> int:
        """Sum over rounds of the declared footprint V_r * M_r — the
        geometric series Theorem 2.1 actually charges a shrinking program
        for (vs rounds * peak for a frozen one)."""
        return sum(max(r, 1) * v * m
                   for r, v, m in self._resolved_footprints())

    def describe(self) -> str:
        """Render the shape schedule, one row per stage.

        >>> p = Plan(name="demo", fingerprint=("demo",), n_nodes=8,
        ...          stages=(PlanStage("entry", 1, 4, None, 8),
        ...                  PlanStage("merge", 1, 8, None, 2),
        ...                  PlanStage("finalize", 1, None, None)),
        ...          prologue=None, epilogue=None, round_bound=3)
        >>> print(p.describe())
        Plan 'demo': V=8, rounds=3 (bound 3), prng=[]
          entry            rounds=1   capacity=4        n_nodes=8
          merge            rounds=1   capacity=8        n_nodes=2
          finalize         rounds=1   capacity=inherit  n_nodes=inherit
        """
        rows = [f"Plan {self.name!r}: V={self.n_nodes}, "
                f"rounds={self.total_rounds} (bound {self.round_bound}), "
                f"prng={list(self.prng_slots)}"]
        for name, rounds, cap, nodes in self.schedule():
            cap_s = "inherit" if cap is None else cap
            nodes_s = "inherit" if nodes is None else nodes
            rows.append(f"  {name:<16} rounds={rounds:<3} "
                        f"capacity={cap_s:<8} n_nodes={nodes_s}")
        return "\n".join(rows)

    def split_key(self, key) -> dict:
        """Resolve the caller's key into one key per declared PRNG slot.

        A single slot receives the key unchanged (bit-compatible with the
        pre-plan entry points); multiple slots split it in declaration
        order.  ``key=None`` falls back to ``PRNGKey(default_seed)``."""
        if not self.prng_slots:
            return {}
        if key is None:
            key = jax.random.PRNGKey(self.default_seed)
        if len(self.prng_slots) == 1:
            return {self.prng_slots[0]: key}
        subkeys = jax.random.split(key, len(self.prng_slots))
        return dict(zip(self.prng_slots, subkeys))


def _check_inputs(plan: Plan, inputs: Tuple) -> None:
    """Fail loudly when runtime inputs disagree with the plan's baked-in
    statics (shapes/dtypes are part of the fingerprint, not of the data)."""
    if plan.input_spec is None:
        return
    if len(inputs) != len(plan.input_spec):
        raise ValueError(
            f"plan {plan.name!r} expects {len(plan.input_spec)} inputs, "
            f"got {len(inputs)}")
    import numpy as np
    for i, (spec, x) in enumerate(zip(plan.input_spec, inputs)):
        if spec is None:
            continue
        shape, dtype = spec
        got = tuple(jnp.shape(x))
        if got != tuple(shape):
            raise ValueError(
                f"plan {plan.name!r} input {i}: expected shape "
                f"{tuple(shape)} (baked into the plan), got {got} — rebuild "
                f"the plan for this size")
        got_dtype = getattr(x, "dtype", None)
        if dtype is not None and got_dtype is not None \
                and np.dtype(got_dtype) != np.dtype(dtype):
            raise ValueError(
                f"plan {plan.name!r} input {i}: expected dtype "
                f"{np.dtype(dtype)} (baked into the plan), got "
                f"{np.dtype(got_dtype)} — rebuild the plan for this dtype")


def execute_plan(plan: Plan, engine, inputs: Tuple, key=None,
                 checkpointer=None):
    """Run a plan's stages in order on ``engine`` and return its outputs.

    Pure whenever the plan's stage bodies are (every builder in this repo):
    safe under ``jax.jit`` / ``jax.vmap`` on array backends, which is what
    :class:`~repro.core.api.Executable` relies on for caching and batching.

    The compiled program names its layers (``jax.named_scope``, read back
    from each op's ``op_name``): ``mr.prologue`` and ``mr.epilogue`` around
    the plan's two ends, and each stage's name around that stage (inside
    it the engine's ``mr.round`` / ``mr.shuffle`` scopes).

    ``checkpointer`` (a :class:`repro.core.recovery.Checkpointer`) turns on
    the ``checkpoint_every`` policy: after each stage the full
    ``{"box", "carry", "accum"}`` state is offered to ``maybe_save`` at that
    stage's cumulative round index, producing the round-boundary snapshots
    :func:`repro.core.recovery.run_plan_with_recovery` /
    :func:`~repro.core.recovery.resume_plan` replay from (DESIGN.md §11).
    Checkpointing is host-side I/O, so it is only meaningful on an eager
    (un-jitted) execution — the compiled ``Executable`` path never passes
    one."""
    _check_inputs(plan, inputs)
    keys = plan.split_key(key)
    with jax.named_scope("mr.prologue"):
        carry = plan.prologue(tuple(inputs), keys)
    state = PlanState(box=None, carry=carry, accum=CostAccum.zero())
    tr = getattr(engine, "tracer", NULL_TRACER)
    if checkpointer is not None:
        from .recovery import _apply_stages
        state = _apply_stages(plan, engine, state, 0, checkpointer)
    elif tr.enabled and not_tracing():
        with tr.span("plan.execute", plan=plan.name, digest=plan_token(plan),
                     backend=getattr(engine, "name", "?")):
            for stage in plan.stages:
                state = apply_stage(plan, engine, stage, state, tr)
    else:
        for stage in plan.stages:
            state = apply_stage(plan, engine, stage, state, tr)
    with jax.named_scope("mr.epilogue"):
        return plan.epilogue(state)


def apply_stage(plan: Plan, engine, stage: PlanStage, state: PlanState,
                tr=NULL_TRACER) -> PlanState:
    """Apply one stage inside its ``plan.stage`` span and its named scope.

    Eager with a live tracer, the span records the stage's declared
    schedule next to its measured round/communication/drop deltas (reading
    them is a host sync — the opt-in cost of tracing), so
    :func:`repro.obs.summary.summarize` can check measured == declared.
    Otherwise the span only opens its profiler annotation (none at jax
    trace time), and nothing is read from the device."""
    with jax.named_scope(stage.name), \
            tr.span("plan.stage", plan=plan.name, stage=stage.name,
                    rounds=stage.rounds, capacity=stage.capacity,
                    n_nodes=stage.n_nodes, shuffles=stage.shuffles) as sp:
        if not (tr.enabled and not_tracing()):
            return stage.apply(engine, state)
        r0 = int(state.accum.rounds)
        c0 = float(state.accum.communication)
        d0 = int(state.accum.dropped)
        state = stage.apply(engine, state)
        sp["measured_rounds"] = int(state.accum.rounds) - r0
        sp["items_sent"] = int(float(state.accum.communication) - c0)
        sp["dropped"] = int(state.accum.dropped) - d0
    return state


# ---------------------------------------------------------------------------
# Stage constructors — the vocabulary the plan builders compose.
# ---------------------------------------------------------------------------

def account_stage(name: str,
                  round_costs: Tuple[Tuple[int, int], ...]) -> PlanStage:
    """Accounting-only rounds with static (items_sent, max_io) per round —
    e.g. the §4.3 pivot-sort rounds, whose cost depends only on (n, M)."""
    costs = tuple((int(i), int(io)) for i, io in round_costs)

    def apply(engine, state: PlanState) -> PlanState:
        acc = state.accum
        for items, io in costs:
            acc = acc.add_round(items_sent=items, max_io=io)
        return state._replace(accum=acc)

    return PlanStage(name, len(costs), None, apply, shuffles=False)


def entry_stage(name: str, n_nodes: int, capacity: int,
                emit: Callable) -> PlanStage:
    """The entry shuffle: ``emit(carry) -> (dests, payload)`` routes the
    input collection into a fresh (n_nodes, capacity) mailbox."""

    def apply(engine, state: PlanState) -> PlanState:
        tr = getattr(engine, "tracer", NULL_TRACER)
        t0 = tr.clock() if tr.enabled else 0.0
        V = engine.aligned_nodes(n_nodes)
        with annotate("engine.round", round=0):
            with jax.named_scope("mr.round"):
                dests, payload = emit(state.carry)
            box, st = engine.shuffle(dests, payload, V, capacity)
        if tr.enabled:
            _round_event(tr, t0, getattr(engine, "name", "?"), 0,
                         V, capacity, st)
        return PlanState(box, state.carry, state.accum.add_round_stats(st))

    return PlanStage(name, 1, capacity, apply, n_nodes)


def round_stage(name: str, make_fn: Callable, n_rounds: int,
                capacity: Optional[int] = None,
                n_nodes: Optional[int] = None,
                early_dests: bool = False) -> PlanStage:
    """``n_rounds`` applications of one round function over the current
    mailbox.  ``make_fn(carry) -> RoundFn`` binds the carry (splitters,
    padded pivots, ...) at execute time; uniform capacity means
    ``LocalEngine`` rolls the rounds into a single ``lax.scan``.

    ``n_nodes`` declares the stage's target mailbox footprint V_r: each
    round shuffles into a ``(n_nodes, capacity)`` mailbox (a *shape-change
    round* when it differs from the current box shape; DESIGN.md §9) —
    the backend's layout granularity is applied at execute time via
    ``engine.aligned_nodes``.  None inherits the current node count.

    ``early_dests=True`` declares that the round function's destinations
    depend only on node ids and the static schedule (never on mailbox
    data), unlocking ShardedEngine's double-buffered round schedule for
    this stage (DESIGN.md §13)."""

    def apply(engine, state: PlanState) -> PlanState:
        V = None if n_nodes is None else engine.aligned_nodes(n_nodes)
        box, accum = engine.run_rounds(make_fn(state.carry), state.box,
                                       n_rounds, capacity=capacity,
                                       accum=state.accum, n_nodes=V,
                                       early_dests=early_dests)
        return state._replace(box=box, accum=accum)

    return PlanStage(name, n_rounds, capacity, apply, n_nodes,
                     early_dests=early_dests)


def compute_stage(name: str, fn: Callable) -> PlanStage:
    """A zero-round transform ``fn(box, carry) -> (box, carry)`` — local
    compute between shuffles (the paper's in-reducer work)."""

    def apply(engine, state: PlanState) -> PlanState:
        with jax.named_scope("mr.round"):
            box, carry = fn(state.box, state.carry)
        return state._replace(box=box, carry=carry)

    return PlanStage(name, 0, None, apply, shuffles=False)


def custom_stage(name: str, rounds: int, capacity: Optional[int],
                 apply: Callable,
                 n_nodes: Optional[int] = None,
                 early_dests: bool = False) -> PlanStage:
    """Escape hatch for stages that drive the engine directly (invisible
    funnels, PRAM steps, BSP supersteps); ``apply(engine, state) -> state``
    must account exactly ``rounds`` rounds.  ``n_nodes`` declares the
    stage's peak physical footprint for the shape schedule (purely
    declarative here — the body drives its own shuffles).  ``early_dests``
    likewise only *declares* overlap legality (DESIGN.md §13): a custom
    body that wants the double-buffered schedule must itself pass the flag
    to ``engine.run_rounds``/``run_stages``.  The body runs inside the
    ``mr.round`` scope; the engine's own ``mr.shuffle`` scope nests inside
    it."""

    def scoped(engine, state: PlanState) -> PlanState:
        with jax.named_scope("mr.round"):
            return apply(engine, state)

    return PlanStage(name, rounds, capacity, scoped, n_nodes,
                     early_dests=early_dests)


__all__ = [
    "Plan", "PlanStage", "PlanState", "execute_plan",
    "account_stage", "compute_stage", "custom_stage",
    "entry_stage", "round_stage",
]
