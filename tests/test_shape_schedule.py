"""Shape-scheduled execution (DESIGN.md §9): declared vs measured footprints.

Pins the per-stage mailbox footprint contract:

- the (V_r, M_r) a plan *declares* per stage equals the physical shapes its
  shuffles actually target on LocalEngine (a recording engine intercepts
  every shuffle call);
- a frozen-shape and a shape-scheduled build of the same plan produce
  bit-identical final outputs and CostAccum on all four backends — only
  the physical padding differs;
- LocalEngine's scan segmentation keeps multi-round shape-changing stages
  jitted (compile-once trace counts);
- the kernel path's guards are re-derived per shuffle call: oversize calls
  fall back to the bit-identical dense shuffle instead of raising, so a
  shape-scheduled program whose entry level exceeds the kernel budget still
  runs its small late levels through the kernel.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (LocalEngine, ReferenceEngine, ShardedEngine,
                        get_engine, hull2d_plan, multisearch_plan,
                        prefix_plan, sort_plan)
from repro.core.funnel import funnel_write_plan
from repro.core.plan import execute_plan

RNG = np.random.default_rng(0)


def four_backends():
    return [ReferenceEngine(), LocalEngine(), ShardedEngine(),
            get_engine("pallas")]


def assert_same_accum(a, b, ctx=""):
    for name, fa, fb in zip(a._fields, a, b):
        assert float(fa) == float(fb), f"{ctx}: CostAccum.{name} {fa} != {fb}"


class RecordingEngine(LocalEngine):
    """LocalEngine that logs the (n_nodes, capacity) of every shuffle."""

    def __init__(self):
        super().__init__()
        self.calls = []

    def shuffle(self, dests, payload, n_nodes, capacity):
        self.calls.append((int(n_nodes), int(capacity)))
        return super().shuffle(dests, payload, n_nodes, capacity)


def declared_footprints(plan):
    """(V_r, M_r) per *physical* round, resolving inherited dims — the
    shapes the engine must be asked for, in execution order (stages with
    ``shuffles=False`` are accounting-only and never hit the engine)."""
    rows, v, m = [], plan.n_nodes, None
    for s in plan.stages:
        v = s.n_nodes if s.n_nodes is not None else v
        m = s.capacity if s.capacity is not None else m
        if s.shuffles:
            rows.extend([(v, m)] * max(s.rounds, 1))
    return rows


class TestDeclaredEqualsMeasured:
    @pytest.mark.parametrize("make_plan", [
        lambda: hull2d_plan(200, 8, shape=True),
        lambda: sort_plan(200, 8, levels=2, shape=True),
        lambda: prefix_plan(200, 8, physical=True, shape=True),
    ], ids=["hull2d", "sort-ladder", "prefix-physical"])
    def test_shuffle_shapes_match_schedule(self, make_plan):
        plan = make_plan()
        eng = RecordingEngine()
        if plan.name == "hull2d":
            inputs = (jnp.asarray(RNG.normal(size=(200, 2))
                                  .astype(np.float32)),)
        elif plan.name == "sort":
            inputs = (jnp.asarray(RNG.normal(size=200).astype(np.float32)),)
        else:
            inputs = (jnp.asarray(RNG.integers(0, 9, 200).astype(np.int32)),)
        execute_plan(plan, eng, inputs, key=jax.random.PRNGKey(0))
        assert eng.calls == declared_footprints(plan)

    def test_measured_mailbox_shrinks_geometrically(self):
        """The hull merge tree's physical V must shrink by the arity per
        level — the whole point of the shape schedule."""
        plan = hull2d_plan(400, 8, shape=True)
        a = max(2, max(2, 8) // 2)
        merge_vs = [s.n_nodes for s in plan.stages
                    if s.name.startswith("merge-")]
        entry_v = plan.n_nodes
        for v in merge_vs:
            entry_v = -(-entry_v // a)
            assert v == entry_v
        frozen = hull2d_plan(400, 8, shape=False)
        assert plan.peak_mailbox_slots() < frozen.peak_mailbox_slots()
        assert plan.total_mailbox_slots() < frozen.total_mailbox_slots()

    def test_total_slots_count_inherited_footprint_rounds(self):
        """A frozen program's steady rounds shuffle at the inherited
        footprint and must be charged for it: frozen total > shaped total
        even when no frozen stage redeclares a dimension."""
        frozen = multisearch_plan(1000, 100, 8, shape=False)
        shaped = multisearch_plan(1000, 100, 8, shape=True)
        # every physical round (all but the accounting-only "output" round)
        # of the frozen DAG runs at the full (V, cap) footprint
        assert frozen.total_mailbox_slots() == \
            (frozen.total_rounds - 1) * frozen.n_nodes * 1000
        assert frozen.total_mailbox_slots() > shaped.total_mailbox_slots()


class TestFrozenVsShapedParity:
    """Bit-identical outputs + CostAccum between frozen and shape-scheduled
    builds of the same plan, on all four backends."""

    @pytest.mark.parametrize("make_engine", [
        ReferenceEngine, LocalEngine, ShardedEngine,
        lambda: get_engine("pallas")], ids=["ref", "local", "sharded",
                                            "pallas"])
    def test_hull2d(self, make_engine):
        eng = make_engine()
        pts = jnp.asarray(RNG.normal(size=(120, 2)).astype(np.float32))
        key = jax.random.PRNGKey(3)
        res = [execute_plan(hull2d_plan(120, 8, shape=s), eng, (pts,),
                            key=key) for s in (False, True)]
        np.testing.assert_array_equal(np.asarray(res[0].points),
                                      np.asarray(res[1].points))
        assert int(res[0].count) == int(res[1].count)
        assert_same_accum(res[0].stats, res[1].stats, ctx=eng.name)

    @pytest.mark.parametrize("make_engine", [
        ReferenceEngine, LocalEngine, ShardedEngine,
        lambda: get_engine("pallas")], ids=["ref", "local", "sharded",
                                            "pallas"])
    def test_sort_ladder(self, make_engine):
        eng = make_engine()
        x = jnp.asarray(RNG.normal(size=120).astype(np.float32))
        key = jax.random.PRNGKey(4)
        res = [execute_plan(sort_plan(120, 8, levels=2, shape=s), eng, (x,),
                            key=key) for s in (False, True)]
        np.testing.assert_array_equal(np.asarray(res[0].values),
                                      np.asarray(res[1].values))
        np.testing.assert_array_equal(np.asarray(res[1].values),
                                      np.sort(np.asarray(x)))
        assert_same_accum(res[0].stats, res[1].stats, ctx=eng.name)

    @pytest.mark.parametrize("make_engine", [
        ReferenceEngine, LocalEngine, ShardedEngine,
        lambda: get_engine("pallas")], ids=["ref", "local", "sharded",
                                            "pallas"])
    def test_prefix_physical(self, make_engine):
        eng = make_engine()
        x = jnp.asarray(RNG.integers(0, 9, 90).astype(np.int32))
        res = [execute_plan(prefix_plan(90, 8, physical=True, shape=s),
                            eng, (x,)) for s in (False, True)]
        np.testing.assert_array_equal(np.asarray(res[0].values),
                                      np.asarray(res[1].values))
        np.testing.assert_array_equal(np.asarray(res[1].values),
                                      np.cumsum(np.asarray(x)))
        assert_same_accum(res[0].stats, res[1].stats, ctx=eng.name)

    def test_multisearch_and_funnel_local(self):
        """The remaining shaped families, pinned on the jit backend (their
        cross-backend parity is already covered by test_conformance)."""
        eng = LocalEngine()
        q = jnp.asarray(RNG.normal(size=80).astype(np.float32))
        piv = jnp.sort(jnp.asarray(RNG.normal(size=12).astype(np.float32)))
        key = jax.random.PRNGKey(5)
        ms = [execute_plan(multisearch_plan(80, 12, 8, shape=s), eng,
                           (q, piv), key=key) for s in (False, True)]
        np.testing.assert_array_equal(np.asarray(ms[0].buckets),
                                      np.asarray(ms[1].buckets))
        assert_same_accum(ms[0].stats, ms[1].stats, ctx="multisearch")

        addrs = jnp.asarray(RNG.integers(0, 16, 64).astype(np.int32))
        vals = jnp.asarray(RNG.normal(size=64).astype(np.float32))
        mem = jnp.zeros(16, jnp.float32)
        fw = [execute_plan(funnel_write_plan(64, 16, 8, jnp.add,
                                             identity=0.0, shape=s),
                           eng, (addrs, vals, mem)) for s in (False, True)]
        np.testing.assert_array_equal(np.asarray(fw[0].memory),
                                      np.asarray(fw[1].memory))
        assert_same_accum(fw[0].stats, fw[1].stats, ctx="funnel")


class TestJitAndScan:
    def test_shaped_plan_compiles_once(self):
        """Shape-change rounds must not break the compile-once contract:
        the whole shrinking program is one jitted callable."""
        eng = LocalEngine()
        pts = jnp.asarray(RNG.normal(size=(150, 2)).astype(np.float32))
        key = jax.random.PRNGKey(0)
        exe = eng.compile(hull2d_plan(150, 8, shape=True))
        r1 = exe(pts, key=key)
        traces = exe.trace_count
        r2 = exe(pts, key=key)
        assert exe.trace_count == traces
        np.testing.assert_array_equal(np.asarray(r1.points),
                                      np.asarray(r2.points))

    def test_run_rounds_shape_change_segments_scan(self):
        """A multi-round stage whose first round changes the mailbox shape:
        the scan and no-scan drivers must agree bit-for-bit."""
        V, cap, V2, R = 8, 3, 2, 4
        entry = jnp.asarray(RNG.integers(-1, V, (V, cap)).astype(np.int32))
        payload = jnp.asarray(RNG.normal(size=(V, cap)).astype(np.float32))

        def fn(r, ids, box):
            # route everything to node (id // 4) in the compact target
            dests = jnp.where(box.valid, (ids // 4)[:, None], -1)
            return dests, box.payload

        outs = []
        for eng in (LocalEngine(), LocalEngine(use_scan=False)):
            box, st = eng.shuffle(entry, payload, V, cap)
            box, acc = eng.run_rounds(fn, box, R, capacity=2 * cap,
                                      n_nodes=V2)
            assert box.n_nodes == V2 and box.capacity == 2 * cap
            outs.append((box, acc))
        np.testing.assert_array_equal(np.asarray(outs[0][0].payload),
                                      np.asarray(outs[1][0].payload))
        np.testing.assert_array_equal(np.asarray(outs[0][0].valid),
                                      np.asarray(outs[1][0].valid))
        assert_same_accum(outs[0][1], outs[1][1], ctx="scan-vs-eager")

    def test_run_stages_accepts_triples(self):
        """run_stages (fn, capacity, n_nodes) triples drive shape changes."""
        eng = LocalEngine()
        dests = jnp.asarray([0, 1, 2, 3], jnp.int32)
        payload = jnp.arange(4.0, dtype=jnp.float32)
        box, _ = eng.shuffle(dests, payload, 4, 2)

        def to_zero(r, ids, b):
            return jnp.where(b.valid, 0, -1), b.payload

        box, acc = eng.run_stages([(to_zero, 4, 1)], box)
        assert box.n_nodes == 1 and box.capacity == 4
        assert int(jnp.sum(box.valid)) == 4


class TestKernelGuardFallback:
    def test_oversize_call_falls_back_to_dense(self, monkeypatch):
        """The pallas engine re-derives the kernel guards per call: a call
        past the counts budget runs the dense shuffle instead of raising,
        bit-identically (budget shrunk so a modest shape exceeds it)."""
        from repro.core import kshuffle as K
        V = 8
        monkeypatch.setattr(K, "_COUNTS_BUDGET", V + 1)  # one tile of counts
        n = 2 * K._TILE_N                                # two tiles: too big
        assert not K.kernel_fits(n, V)
        eng = get_engine("pallas")
        dests = jnp.asarray(RNG.integers(0, V, n).astype(np.int32))
        payload = jnp.asarray(RNG.normal(size=n).astype(np.float32))
        eng.route_log.reset()
        box_k, st_k = eng.shuffle(dests, payload, V, 4)
        assert eng.route_log.snapshot() == (0, 1)
        box_d, st_d = LocalEngine().shuffle(dests, payload, V, 4)
        np.testing.assert_array_equal(np.asarray(box_k.payload),
                                      np.asarray(box_d.payload))
        np.testing.assert_array_equal(np.asarray(box_k.valid),
                                      np.asarray(box_d.valid))
        for fa, fb in zip(st_k, st_d):
            assert int(fa) == int(fb)

    def test_kernel_fits_new_guards(self):
        from repro.core.kshuffle import kernel_fits
        assert kernel_fits(100, 8)
        # the old single-tile and int32-key cliffs are gone...
        assert kernel_fits((1 << 18) + 1, 4)
        assert kernel_fits(300000, 8191)
        # ...what remains: node count within VMEM and the counts budget
        assert not kernel_fits(100, 1 << 21)
        assert not kernel_fits((1 << 27) + 1, 1023)
