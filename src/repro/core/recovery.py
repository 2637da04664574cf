"""Fault-injected, checkpointed round execution with bit-identical recovery.

Fault tolerance is MapReduce's founding motivation (Dean & Ghemawat's
original system re-executes failed map tasks), and the round-based model of
Theorem 2.1 makes the unit of recovery explicit: the **round boundary**.
Between rounds the entire computation state is one mailbox plus a functional
cost accumulator — there is nothing else to capture — so a checkpoint taken
at a round boundary is a complete, replayable snapshot, and "BSP vs
MapReduce" (arXiv 1203.2081) argues these per-round synchronization points
are precisely the model's defining cost structure.  This module turns that
observation into machinery (DESIGN.md §11):

- :class:`FaultConfig` / :class:`FaultInjector` — seeded per-(round, shard)
  failure and straggler injection, modeled on the
  ``FAILURE_PROBABILITY`` / ``STRAGGLER_PROBABILITY`` simulator config of
  SNIPPETS.md #1.  Draws are keyed by a monotonic *attempt* counter, so a
  replayed round gets a fresh draw — with p < 1 progress is guaranteed,
  exactly like task re-execution in the real system.
- :class:`FaultInjectingEngine` — a backend-agnostic proxy that interposes
  the injector in front of any engine's Shuffle step (Reference, Local,
  Sharded, and the Pallas kernel variant alike; round loops run eagerly so
  every shuffle is a host-observable fault point).
- :class:`Checkpointer` — round-boundary checkpointing of the
  ``(payload, validity, CostAccum)`` tuple keyed by
  ``(plan fingerprint, round index)``, reusing the step-atomic
  tmp-dir-then-rename protocol of :mod:`repro.train.checkpoint` (a crash
  mid-save leaves the previous checkpoint intact).
- :func:`run_plan_with_recovery` / :func:`resume_plan` — recovery by
  replaying from the last checkpoint.  Because every backend's round
  execution is deterministic and bit-identical (the conformance suite's
  contract), a recovered run produces **bit-identical outputs and cost
  accounting** to a fault-free run: the accumulator is restored from the
  checkpoint, so replayed rounds are never double-counted.
- **Elastic resume** — checkpoints store the gathered logical mailbox, so a
  program checkpointed at one shard count restarts at another:
  :func:`realign_mailbox` re-pads the node axis to the new engine's
  ``aligned_nodes`` granularity and the plan's stages re-derive their
  shape-scheduled ``(V_r, M_r)`` footprints against the new mesh at execute
  time (DESIGN.md §9).  :func:`elastic_engine` builds a
  :class:`~repro.core.engine.ShardedEngine` over the first ``n`` healthy
  devices, refusing (like ``repro.train.elastic.plan_mesh``) to silently
  shrink an overcommitted request.

Typical use::

    from repro.core import LocalEngine, sort_plan
    from repro.core.recovery import (Checkpointer, FaultConfig,
                                     run_plan_with_recovery)

    engine = LocalEngine()
    plan = sort_plan(4096, 64, align=engine.aligned_nodes)
    ck = Checkpointer("/tmp/ckpts", plan=plan, every=2)
    out, report = run_plan_with_recovery(
        plan, engine, (x,),
        faults=FaultConfig(failure_probability=0.05, seed=0),
        checkpointer=ck)
    # out is bit-identical to engine.compile(plan)(x); report says how many
    # rounds were replayed and how many checkpoints were written.
"""
from __future__ import annotations

import base64
import dataclasses
import hashlib
import json
import pathlib
import pickle
import shutil
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .costmodel import CostAccum
from .engine import MREngine, ShardedEngine
from .mrmodel import Mailbox
from .plan import Plan, PlanState, apply_stage
from ..obs import NULL_TRACER, Tracer, plan_token
from ..train import checkpoint as _ckpt


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------

class FaultError(RuntimeError):
    """Base class of injected execution faults."""


class ShardFailure(FaultError):
    """A shard died mid-round (the classic MapReduce worker failure).

    Raised by the injection layer *before* the shuffle executes, so a failed
    round leaves no partial state — exactly the paper model's all-or-nothing
    round semantics.  ``round_index`` is the monotonic shuffle-attempt
    ordinal at which the failure fired (it never repeats across replays)."""

    def __init__(self, round_index: int, shard: int):
        super().__init__(
            f"injected shard failure: shard {shard} died at shuffle "
            f"attempt {round_index}")
        self.round_index = int(round_index)
        self.shard = int(shard)


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Knobs of the injection layer (SNIPPETS.md #1's simulator config).

    ``failure_probability`` / ``straggler_probability`` are per-(attempt,
    shard) Bernoulli rates drawn from a PRNG seeded by
    ``(seed, attempt, shard)`` — fully deterministic, machine-independent.
    ``fail_at`` adds explicit deterministic failures: shuffle-attempt
    ordinals (0-based, counted across replays, so each fires exactly once).
    ``max_failures`` caps total injected failures (None = unbounded);
    stragglers never fail a round — they only accrue simulated delay in the
    injector's event log (``straggler_delay_s`` virtual seconds each), so
    outputs and cost accounting stay bit-identical to a fault-free run."""

    failure_probability: float = 0.0
    straggler_probability: float = 0.0
    straggler_delay_s: float = 0.05
    seed: int = 0
    fail_at: Tuple[int, ...] = ()
    fail_shard: int = 0
    max_failures: Optional[int] = None


class FaultInjector:
    """Seeded fault source shared by one engine proxy across replays.

    ``calls`` is the monotonic shuffle-attempt counter.  Injected events are
    recorded as ``fault.failure`` / ``fault.straggler`` obs events into a
    private :class:`repro.obs.Tracer` sink — and mirrored into the bound
    engine tracer when one is live (``tracer``, auto-wired by
    :class:`FaultInjectingEngine`) — so traces, the fault benchmark, and
    tests all read one stream.  The legacy ``events`` attribute survives as
    a read-only view of that sink (``(kind, attempt, shard)`` tuples)."""

    def __init__(self, config: FaultConfig, tracer=None):
        self.config = config
        self.calls = 0
        self.failures = 0
        self.stragglers = 0
        self.simulated_delay_s = 0.0
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._sink = Tracer()

    @property
    def events(self):
        """Legacy audit view: ``(kind, attempt, shard)`` per injected event,
        reconstructed from the obs event sink."""
        return [(e.kind.split(".", 1)[1], e.attrs["attempt"],
                 e.attrs["shard"]) for e in self._sink.events()]

    def _emit(self, kind: str, **attrs) -> None:
        self._sink.event(kind, **attrs)
        tr = self.tracer
        if tr.enabled:
            tr.event(kind, **attrs)
            tr.count(f"{kind}s")

    def _budget_left(self) -> bool:
        mf = self.config.max_failures
        return mf is None or self.failures < mf

    def _fail(self, attempt: int, shard: int):
        self.failures += 1
        self._emit("fault.failure", attempt=attempt, shard=shard)
        raise ShardFailure(attempt, shard)

    def on_shuffle(self, n_shards: int) -> None:
        """One shuffle attempt: maybe raise :class:`ShardFailure`, maybe log
        straggler events.  Called by the proxy before the real shuffle."""
        cfg = self.config
        attempt = self.calls
        self.calls += 1
        if attempt in cfg.fail_at and self._budget_left():
            self._fail(attempt, cfg.fail_shard % max(1, n_shards))
        if cfg.failure_probability <= 0 and cfg.straggler_probability <= 0:
            return
        for shard in range(max(1, n_shards)):
            rng = np.random.default_rng([cfg.seed, attempt, shard])
            u = float(rng.random())
            if u < cfg.failure_probability:
                if self._budget_left():
                    self._fail(attempt, shard)
            elif u < cfg.failure_probability + cfg.straggler_probability:
                self.stragglers += 1
                self.simulated_delay_s += cfg.straggler_delay_s
                self._emit("fault.straggler", attempt=attempt, shard=shard,
                           delay_s=cfg.straggler_delay_s)


class FaultInjectingEngine(MREngine):
    """Backend-agnostic injection proxy: ``inner``'s shuffle behind a
    :class:`FaultInjector`.

    Round drivers (``run_round``/``run_rounds``/``run_stages``) use the
    eager :class:`MREngine` base implementations — never the inner
    backend's ``lax.scan`` roll-up — so every shuffle is a host-level call
    the injector can interpose (``jittable = vmappable = False``).  The
    shuffle itself, and layout decisions (``aligned_nodes``), delegate to
    the wrapped engine, so semantics are bit-identical to running ``inner``
    directly whenever no fault fires."""

    jittable = False
    vmappable = False

    def __init__(self, engine: MREngine, faults):
        self.inner = engine
        self.injector = (faults if isinstance(faults, FaultInjector)
                         else FaultInjector(faults))
        self.name = f"faulty-{engine.name}"
        self.n_shards = getattr(engine, "n_shards", 1)
        # MREngine defines `tracer` as a class attribute, so __getattr__
        # below would never delegate it — adopt the inner engine's tracer
        # explicitly, and hand it to the injector so fault events land in
        # the same trace as the rounds they kill.
        self.tracer = getattr(engine, "tracer", NULL_TRACER)
        if self.tracer.enabled and not self.injector.tracer.enabled:
            self.injector.tracer = self.tracer

    def aligned_nodes(self, n_nodes: int) -> int:
        return self.inner.aligned_nodes(n_nodes)

    def node_ids(self, n_nodes: int):
        return self.inner.node_ids(n_nodes)

    def __getattr__(self, attr):
        # Backend-specific attributes stage bodies probe (mesh, axis_name,
        # shuffle_impl, ...) resolve against the wrapped engine.
        return getattr(self.inner, attr)

    def shuffle(self, dests, payload, n_nodes: int, capacity: int):
        self.injector.on_shuffle(self.n_shards)
        return self.inner.shuffle(dests, payload, n_nodes, capacity)


def with_faults(engine: MREngine, faults) -> FaultInjectingEngine:
    """Wrap ``engine`` with a :class:`FaultConfig` (or a live
    :class:`FaultInjector`, to share attempt counters across drivers)."""
    return FaultInjectingEngine(engine, faults)


# ---------------------------------------------------------------------------
# Round-boundary checkpointing
# ---------------------------------------------------------------------------

_KINDS = ("array", "int", "float", "bool", "str", "bytes")


def _leaf_kind(leaf) -> str:
    if isinstance(leaf, bool):
        return "bool"
    if isinstance(leaf, int):
        return "int"
    if isinstance(leaf, float):
        return "float"
    if isinstance(leaf, str):
        return "str"
    if isinstance(leaf, bytes):
        return "bytes"
    return "array"


def _cast_leaf(kind: str, arr: np.ndarray):
    if kind == "int":
        return int(arr)
    if kind == "float":
        return float(arr)
    if kind == "bool":
        return bool(arr)
    if kind == "str":
        return str(arr)
    if kind == "bytes":
        return bytes(arr)
    return jnp.asarray(arr)


def plan_digest(plan: Plan) -> str:
    """Stable short digest of ``(plan.fingerprint, plan.shape_fingerprint)``
    — the on-disk half of the (plan fingerprint, round index) checkpoint
    key.  Two plans that would not share a compiled executable never share
    a checkpoint directory."""
    token = repr((plan.fingerprint, plan.shape_fingerprint))
    return hashlib.sha1(token.encode("utf-8")).hexdigest()[:16]


class Checkpointer:
    """Round-boundary checkpoints keyed by (plan fingerprint, round index).

    On-disk layout (reusing :func:`repro.train.checkpoint.save`'s
    step-atomic tmp-dir-then-rename protocol, so a crash mid-save never
    corrupts the last durable checkpoint)::

        <directory>/plan_<digest>/step_<round:08d>/
            <i>_leaf_....npy     # one per pytree leaf, gathered to host
            manifest.json        # shapes/dtypes + treedef + leaf kinds

    The checkpointed tree is the full round-boundary state — the mailbox
    ``(payload, validity)``, the plan carry, and the functional
    :class:`~repro.core.costmodel.CostAccum` — flattened to enumerated
    leaves; the pytree structure travels in the manifest (pickled treedef,
    base64) next to a per-leaf kind tag so Python scalars (static shapes,
    capacities) restore as scalars, not 0-d arrays.  Checkpoints are
    topology-agnostic: leaves are gathered logical arrays, so a restore may
    land on a different backend or shard count (see
    :func:`realign_mailbox`).

    ``every`` is the ``checkpoint_every`` policy: :meth:`maybe_save`
    persists only when at least ``every`` rounds completed since the last
    durable checkpoint.  ``keep`` (optional) prunes the oldest checkpoints
    beyond the newest ``keep``.

    ``async_save=True`` routes saves through
    :class:`repro.train.checkpoint.AsyncSaver`: the round loop is blocked
    only for the device→host snapshot (device_get on the caller thread);
    the ``.npy`` writes and the atomic publish happen on a background
    thread, overlapping the next rounds' device compute — the
    checkpoint-I/O counterpart of the DESIGN.md §13 round overlap.  One
    save may be outstanding at a time; the next save (or any read —
    :meth:`rounds`/:meth:`latest`/:meth:`load` — or an explicit
    :meth:`flush`) settles it first, accounting its bytes, emitting its
    ``ckpt.save`` event, and re-raising any background write error.  The
    on-disk format, the ``every`` cadence, and recovery semantics are
    identical to the synchronous default.
    """

    def __init__(self, directory, plan: Optional[Plan] = None, *,
                 every: int = 1, keep: Optional[int] = None,
                 tag: Optional[str] = None, tracer=None,
                 async_save: bool = False):
        if plan is None and tag is None:
            raise ValueError("Checkpointer needs a plan (fingerprint key) "
                             "or an explicit tag")
        if int(every) < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        digest = plan_digest(plan) if plan is not None else \
            hashlib.sha1(str(tag).encode("utf-8")).hexdigest()[:16]
        self.root = pathlib.Path(directory) / f"plan_{digest}"
        self.every = int(every)
        self.keep = None if keep is None else int(keep)
        self.saved_rounds = []
        self.bytes_written = 0
        self._last_saved = 0
        self.async_save = bool(async_save)
        self._saver = _ckpt.AsyncSaver() if self.async_save else None
        self._pending_round = None
        # ckpt.save / ckpt.restore sink; the recovery drivers re-wire this
        # to the engine's tracer when one is live (opt-in, like every hook).
        self.tracer = NULL_TRACER if tracer is None else tracer

    # -- policy --------------------------------------------------------------
    def due(self, rounds_done: int) -> bool:
        """Whether ``rounds_done`` completed rounds warrant a checkpoint
        under the ``every`` policy (measured from the last durable save)."""
        return rounds_done - self._last_saved >= self.every

    def maybe_save(self, rounds_done: int, tree, meta=None) -> bool:
        """Checkpoint iff :meth:`due`; returns whether a save happened."""
        if not self.due(rounds_done):
            return False
        self.save(rounds_done, tree, meta=meta)
        return True

    # -- storage -------------------------------------------------------------
    def save(self, round_idx: int, tree, meta=None) -> str:
        """Persist ``tree`` as the round-``round_idx`` checkpoint
        (step-atomic; overwrites an existing checkpoint of the same round).

        Synchronous by default.  With ``async_save`` the device→host
        snapshot happens here (so the returned state is consistent no
        matter what the round loop does next) but the disk write runs on
        the saver's background thread; the returned path is where the
        checkpoint *will* be published — settle with :meth:`flush` before
        reading it."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        kinds = [_leaf_kind(l) for l in leaves]
        flat = {f"leaf_{i:05d}": np.asarray(jax.device_get(l))
                for i, l in enumerate(leaves)}
        extra = {"treedef_b64": base64.b64encode(
                     pickle.dumps(treedef)).decode("ascii"),
                 "leaf_kinds": kinds,
                 **(meta or {})}
        if self.async_save:
            # Settle the previous outstanding save first: account its
            # bytes, emit its ckpt.save event, surface any write error.
            self._settle()
            self._saver.save_async(str(self.root), int(round_idx), flat,
                                   extra_meta=extra)
            self._pending_round = int(round_idx)
            path = str(self.root / f"step_{int(round_idx):08d}")
        else:
            path = _ckpt.save(str(self.root), int(round_idx), flat,
                              extra_meta=extra)
            self._account(int(round_idx), path)
        self.saved_rounds.append(int(round_idx))
        self._last_saved = int(round_idx)
        return path

    def _account(self, round_idx: int, path) -> None:
        """Fold one *published* checkpoint into the byte counters, the
        tracer, and the ``keep`` pruning policy."""
        nbytes = sum(p.stat().st_size
                     for p in pathlib.Path(path).glob("*.npy"))
        self.bytes_written += nbytes
        if self.tracer.enabled:
            self.tracer.event("ckpt.save", round=int(round_idx),
                              bytes=nbytes)
            self.tracer.count("ckpt.saves")
        if self.keep is not None:
            self._prune()

    def _settle(self) -> None:
        if self._saver is None:
            return
        self._saver.wait()           # joins the writer; re-raises its error
        if self._pending_round is not None:
            self._account(self._pending_round, self._saver.last_path)
            self._pending_round = None

    def flush(self) -> None:
        """Block until any outstanding async save is durably published and
        accounted (no-op for the synchronous default).  Re-raises an error
        the background writer hit."""
        self._settle()

    def _prune(self) -> None:
        steps = sorted(self.rounds())
        for r in steps[:max(0, len(steps) - self.keep)]:
            shutil.rmtree(self.root / f"step_{r:08d}", ignore_errors=True)

    def rounds(self):
        """Round indices with a durable checkpoint, ascending."""
        self._settle()
        if not self.root.exists():
            return []
        return sorted(int(p.name.split("_")[1]) for p in self.root.iterdir()
                      if p.is_dir() and p.name.startswith("step_"))

    def latest(self) -> Optional[int]:
        """Newest durable round index (None when nothing was saved)."""
        self._settle()
        return _ckpt.latest_step(str(self.root))

    def load(self, round_idx: int) -> Tuple[Any, Dict[str, Any]]:
        """Restore the round-``round_idx`` checkpoint: returns
        ``(tree, meta)`` with array leaves as jnp arrays and scalar leaves
        cast back to their Python types."""
        self._settle()
        final = self.root / f"step_{int(round_idx):08d}"
        manifest = json.loads((final / "manifest.json").read_text())
        meta = manifest["meta"]
        treedef = pickle.loads(base64.b64decode(meta["treedef_b64"]))
        leaves = []
        for i, kind in enumerate(meta["leaf_kinds"]):
            info = manifest["tensors"][f"leaf_{i:05d}"]
            arr = np.load(final / info["file"], allow_pickle=False)
            leaves.append(_cast_leaf(kind, arr))
        if self.tracer.enabled:
            self.tracer.event("ckpt.restore", round=int(round_idx),
                              stage_index=meta.get("stage_index"))
            self.tracer.count("ckpt.restores")
        return jax.tree_util.tree_unflatten(treedef, leaves), meta


# ---------------------------------------------------------------------------
# Recovery drivers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RecoveryReport:
    """What recovery actually did — the observability half of the story."""

    restarts: int = 0
    rounds_replayed: int = 0
    checkpoints_written: int = 0
    checkpoint_bytes: int = 0
    failures_injected: int = 0
    stragglers_injected: int = 0
    simulated_delay_s: float = 0.0
    resumed_at_round: Optional[int] = None


def realign_mailbox(box: Mailbox, engine: MREngine) -> Mailbox:
    """Re-pad a restored mailbox's node axis to ``engine``'s layout
    granularity (``aligned_nodes``).

    Checkpoints store the gathered logical mailbox of whatever engine wrote
    them; a resume engine with a coarser granularity (more shards) needs
    V to be a multiple of its shard count.  Appending all-invalid node rows
    is semantics-neutral: round functions emit -1 ("no item") for invalid
    slots, and the shape-scheduled stages re-derive their own (V_r, M_r)
    targets via ``engine.aligned_nodes`` at execute time, so the first
    shape-change round re-compacts the mailbox anyway."""
    V = box.n_nodes
    target = engine.aligned_nodes(V)
    if target == V:
        return box
    pad = target - V

    def pad_leaf(leaf):
        leaf = jnp.asarray(leaf)
        return jnp.concatenate(
            [leaf, jnp.zeros((pad,) + leaf.shape[1:], leaf.dtype)], axis=0)

    return Mailbox(
        payload=jax.tree_util.tree_map(pad_leaf, box.payload),
        valid=jnp.concatenate(
            [jnp.asarray(box.valid),
             jnp.zeros((pad, box.capacity), bool)], axis=0))


def elastic_engine(n_shards: int, axis_name: str = "nodes",
                   shuffle_impl: str = "dense") -> ShardedEngine:
    """A :class:`~repro.core.engine.ShardedEngine` over the first
    ``n_shards`` healthy devices — the MR counterpart of
    ``repro.train.elastic.plan_mesh``.  Raises (healthy vs requested)
    instead of silently shrinking an elastic resume."""
    devs = jax.devices()
    if int(n_shards) < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if int(n_shards) > len(devs):
        raise ValueError(
            f"elastic_engine: requested {n_shards} shards but only "
            f"{len(devs)} devices are healthy — refusing to silently "
            f"shrink the resume topology")
    mesh = jax.make_mesh((int(n_shards),), (axis_name,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devs[:int(n_shards)])
    return ShardedEngine(axis_name=axis_name, mesh=mesh,
                         shuffle_impl=shuffle_impl)


def _cumulative_rounds(plan: Plan):
    out, c = [], 0
    for s in plan.stages:
        c += s.rounds
        out.append(c)
    return out


def _fresh_state(plan: Plan, inputs, key) -> PlanState:
    from .plan import _check_inputs
    _check_inputs(plan, tuple(inputs))
    keys = plan.split_key(key)
    carry = plan.prologue(tuple(inputs), keys)
    return PlanState(box=None, carry=carry, accum=CostAccum.zero())


def _state_tree(state: PlanState):
    return {"box": state.box, "carry": state.carry, "accum": state.accum}


def _state_from_tree(tree) -> PlanState:
    return PlanState(box=tree["box"], carry=tree["carry"],
                     accum=tree["accum"])


def _wire_tracer(checkpointer: Optional[Checkpointer], tr) -> None:
    """Point an un-traced checkpointer at the engine's live tracer so
    ckpt.* events land in the same stream as the rounds they snapshot."""
    if (checkpointer is not None and tr.enabled
            and not checkpointer.tracer.enabled):
        checkpointer.tracer = tr


def _staged_apply(plan: Plan, engine, i: int, state: PlanState,
                  tr) -> PlanState:
    """Stage ``i`` under its ``plan.stage`` span (:func:`repro.core.plan.
    apply_stage`).  A stage killed mid-apply by an injected fault records
    its span with ``aborted=True`` (see obs trace module)."""
    return apply_stage(plan, engine, plan.stages[i], state, tr)


def _apply_stages(plan: Plan, engine, state: PlanState, start: int,
                  checkpointer: Optional[Checkpointer],
                  report: Optional[RecoveryReport] = None) -> PlanState:
    """Run stages ``start..`` with round-boundary checkpoints (the shared
    body of ``execute_plan(checkpointer=...)`` and the recovery loop)."""
    cum = _cumulative_rounds(plan)
    tr = getattr(engine, "tracer", NULL_TRACER)
    _wire_tracer(checkpointer, tr)
    for i in range(start, len(plan.stages)):
        state = _staged_apply(plan, engine, i, state, tr)
        if checkpointer is not None:
            saved = checkpointer.maybe_save(
                cum[i], _state_tree(state),
                meta={"stage_index": i, "plan": plan.name,
                      "rounds_done": cum[i]})
            if saved and report is not None:
                report.checkpoints_written += 1
    return state


def _drive(plan: Plan, base_engine, eng, state: PlanState, start: int,
           inputs, key, checkpointer: Optional[Checkpointer],
           max_restarts: int, report: RecoveryReport) -> PlanState:
    """The recovery loop: execute, and on an injected fault replay from the
    last durable round-boundary checkpoint (or from scratch)."""
    cum = _cumulative_rounds(plan)
    done = cum[start - 1] if start > 0 and cum else 0
    tr = getattr(eng, "tracer", NULL_TRACER)
    _wire_tracer(checkpointer, tr)
    with tr.span("plan.execute", plan=plan.name, digest=plan_token(plan),
                 backend=getattr(eng, "name", "?")):
        while True:
            try:
                for i in range(start, len(plan.stages)):
                    state = _staged_apply(plan, eng, i, state, tr)
                    done = cum[i]
                    if checkpointer is not None:
                        saved = checkpointer.maybe_save(
                            done, _state_tree(state),
                            meta={"stage_index": i, "plan": plan.name,
                                  "rounds_done": done})
                        if saved:
                            report.checkpoints_written += 1
                return state
            except FaultError:
                report.restarts += 1
                if report.restarts > max_restarts:
                    raise
                last = (checkpointer.latest()
                        if checkpointer is not None else None)
                if last is None:
                    state = _fresh_state(plan, inputs, key)
                    start = 0
                    report.rounds_replayed += done
                    done = 0
                else:
                    tree, meta = checkpointer.load(last)
                    state = _state_from_tree(tree)
                    if state.box is not None:
                        state = state._replace(
                            box=realign_mailbox(state.box, base_engine))
                    start = int(meta["stage_index"]) + 1
                    report.rounds_replayed += max(0, done - int(last))
                    done = int(last)
                if tr.enabled:
                    tr.event("recover.restart", restarts=report.restarts,
                             from_round=done)
                    tr.count("recover.restarts")


def _finish(plan, state, report, eng, checkpointer):
    outputs = plan.epilogue(state)
    if isinstance(eng, FaultInjectingEngine):
        inj = eng.injector
        report.failures_injected = inj.failures
        report.stragglers_injected = inj.stragglers
        report.simulated_delay_s = inj.simulated_delay_s
    if checkpointer is not None:
        checkpointer.flush()         # settle an outstanding async save
        report.checkpoint_bytes = checkpointer.bytes_written
    return outputs, report


def run_plan_with_recovery(plan: Plan, engine: MREngine, inputs,
                           key=None, *, faults=None,
                           checkpointer: Optional[Checkpointer] = None,
                           max_restarts: int = 8):
    """Execute ``plan`` on ``engine`` under fault injection with
    round-boundary checkpointing and replay recovery.

    Returns ``(outputs, RecoveryReport)`` where ``outputs`` is bit-identical
    (values *and* cost accounting) to a fault-free
    ``execute_plan(plan, engine, inputs, key)``: the accumulator is part of
    every checkpoint, so replayed rounds are counted exactly once.  With
    ``faults=None`` and ``checkpointer=None`` this *is* ``execute_plan``
    plus an empty report.  ``max_restarts`` bounds replays; the fault that
    exceeds it propagates (checkpoints already written stay durable — hand
    the directory to :func:`resume_plan`, on this or any other engine)."""
    eng = with_faults(engine, faults) if faults is not None else engine
    report = RecoveryReport()
    state = _fresh_state(plan, inputs, key)
    state = _drive(plan, engine, eng, state, 0, inputs, key,
                   checkpointer, int(max_restarts), report)
    return _finish(plan, state, report, eng, checkpointer)


def resume_plan(plan: Plan, engine: MREngine, inputs, key=None, *,
                checkpointer: Checkpointer, at_round: Optional[int] = None,
                faults=None, max_restarts: int = 8):
    """Restart a checkpointed program — possibly on a different backend or
    shard count (elastic resume).

    Loads the newest checkpoint under ``checkpointer`` (or the explicit
    ``at_round``), re-pads the mailbox to ``engine``'s layout granularity
    via :func:`realign_mailbox`, and drives the remaining stages; the
    shape-scheduled per-stage footprints are re-derived for the new engine
    through ``engine.aligned_nodes`` at execute time (DESIGN.md §9).
    ``inputs``/``key`` must be the originals — they are only consulted if a
    later fault forces a from-scratch replay.  Returns
    ``(outputs, RecoveryReport)`` bit-identical to the fault-free run."""
    last = at_round if at_round is not None else checkpointer.latest()
    if last is None:
        raise ValueError(
            f"resume_plan: no checkpoint under {checkpointer.root} — "
            f"run_plan_with_recovery writes them")
    tree, meta = checkpointer.load(last)
    state = _state_from_tree(tree)
    if state.box is not None:
        state = state._replace(box=realign_mailbox(state.box, engine))
    start = int(meta["stage_index"]) + 1
    eng = with_faults(engine, faults) if faults is not None else engine
    report = RecoveryReport(resumed_at_round=int(last))
    state = _drive(plan, engine, eng, state, start, inputs, key,
                   checkpointer, int(max_restarts), report)
    return _finish(plan, state, report, eng, checkpointer)


__all__ = [
    "FaultConfig", "FaultError", "FaultInjector", "FaultInjectingEngine",
    "ShardFailure", "with_faults",
    "Checkpointer", "plan_digest", "RecoveryReport",
    "run_plan_with_recovery", "resume_plan",
    "realign_mailbox", "elastic_engine",
]
