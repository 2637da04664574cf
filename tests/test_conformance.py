"""Cross-backend differential conformance suite.

Seeded-numpy randomized round programs and algorithm instances (sort,
multisearch, 2-D/3-D hull, fixed-dim LP) executed on ReferenceEngine,
LocalEngine (scan and no-scan), ShardedEngine (axis size 1 in-process;
multi-shard parity lives in test_distributed.py) and the Pallas
kernel-shuffle column (``get_engine("pallas")`` — interpret mode off TPU,
the same control flow the Mosaic lowering compiles), asserting

- bit-identical mailboxes / outputs,
- FIFO and overflow/drop parity (the w.h.p. failure event is *reported
  identically*, never divergently), and
- matching functional CostAccum round/communication/drop counts.

No hypothesis — seeded ``parametrize`` only, sized to stay well inside the
tier-1 budget (ReferenceEngine is a per-item host loop).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (CostAccum, LocalEngine, ReferenceEngine,
                        ShardedEngine, convex_hull_2d_mr, convex_hull_3d_mr,
                        get_engine, linear_program_mr, sample_sort_mr)


def engines():
    return [ReferenceEngine(), LocalEngine(), LocalEngine(use_scan=False),
            ShardedEngine(), get_engine("pallas")]


def instance_engines():
    """The four-substrate matrix for the expensive algorithm instances:
    Reference / Local / Sharded / Pallas-kernel.  The scan-vs-no-scan
    LocalEngine split is a driver detail, not a shuffle substrate — its
    parity is pinned by the cheap random-program tests above and
    test_engine.py, so the instances skip that column to stay inside the
    tier-1 wall-time budget."""
    return [ReferenceEngine(), LocalEngine(), ShardedEngine(),
            get_engine("pallas")]


def assert_same_box(ref, got, ctx=""):
    for la, lb in zip(jax.tree_util.tree_leaves(ref.payload),
                      jax.tree_util.tree_leaves(got.payload)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(got.valid),
                                  err_msg=ctx)


def assert_same_accum(ref: CostAccum, got: CostAccum, ctx=""):
    for name, fa, fb in zip(ref._fields, ref, got):
        assert float(fa) == float(fb), f"{ctx}: CostAccum.{name} {fa} != {fb}"


class TestRandomRoundProgramConformance:
    """Randomized table-driven programs: arbitrary dests (including drops
    and 'no item' holes) must shuffle identically everywhere."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_program_parity(self, seed):
        rng = np.random.default_rng(seed)
        V = int(rng.integers(4, 10))
        cap = int(rng.integers(2, 5))
        n_rounds = 3
        entry_dests = rng.integers(-1, V, size=(V, cap)).astype(np.int32)
        payload = rng.normal(size=(V, cap)).astype(np.float32)
        tables = jnp.asarray(
            rng.integers(-1, V, size=(n_rounds, V, cap)).astype(np.int32))

        def fn(r, ids, box):
            dests = jnp.where(box.valid, tables[r], -1)
            return dests, box.payload

        ref_box = ref_acc = None
        for e in engines():
            box, st = e.shuffle(entry_dests, payload, V, cap)
            acc = CostAccum.zero().add_round_stats(st)
            for r in range(n_rounds):
                box, st = e.run_round(fn, box, r)
                acc = acc.add_round_stats(st)
            if ref_box is None:
                ref_box, ref_acc = box, acc
            else:
                assert_same_box(ref_box, box, ctx=f"seed={seed} {e.name}")
                assert_same_accum(ref_acc, acc, ctx=f"seed={seed} {e.name}")

    def test_forced_overflow_fifo_parity(self):
        """Funnel 3x the capacity into two nodes: every backend must keep
        the same FIFO prefix and count the same drops."""
        V, cap = 4, 3
        dests = np.asarray([0, 1, 0, 0, 1, 0, 1, 0, 1, 0, 1, 0], np.int32)
        payload = np.arange(12, dtype=np.float32)
        ref_box = ref_st = None
        for e in engines():
            box, st = e.shuffle(dests, payload, V, cap)
            assert int(st.dropped) == 6, e.name
            if ref_box is None:
                ref_box, ref_st = box, st
            else:
                assert_same_box(ref_box, box, ctx=e.name)
                for fa, fb in zip(ref_st, st):
                    assert int(fa) == int(fb), e.name


class TestEmptyAndDegenerateShuffles:
    """n = 0 flattened items and V = 1 mailboxes — the degenerate shapes
    shape-scheduled programs produce at their smallest levels — must
    shuffle identically (and without crashing) on every backend."""

    @pytest.mark.parametrize("dests_shape,V,cap", [
        ((0,), 1, 2),          # empty 1-D entry send into one node
        ((0,), 4, 2),          # empty 1-D entry send, several nodes
        ((0, 3), 1, 2),        # empty (V, M) mailbox send (zero source rows)
        ((0, 3), 4, 3),
        ((5,), 1, 2),          # V = 1: everything funnels into one node
    ], ids=["n0-V1", "n0-V4", "2d-empty-V1", "2d-empty-V4", "V1-overflow"])
    def test_empty_and_single_node_parity(self, dests_shape, V, cap):
        n = int(np.prod(dests_shape))
        dests = np.zeros(dests_shape, np.int32)
        payload = np.arange(float(n), dtype=np.float32).reshape(dests_shape)
        ref_box = ref_st = None
        for e in engines():
            box, st = e.shuffle(dests, payload, V, cap)
            assert np.asarray(box.valid).shape == (V, cap), e.name
            if ref_box is None:
                ref_box, ref_st = box, st
            else:
                assert_same_box(ref_box, box, ctx=f"{e.name} {dests_shape}")
                for name, fa, fb in zip(ref_st._fields, ref_st, st):
                    assert int(fa) == int(fb), (e.name, name)
        # V=1 oversubscription keeps the FIFO prefix and counts the drops
        if n and V == 1:
            assert int(ref_st.dropped) == n - cap
            np.testing.assert_array_equal(
                np.asarray(ref_box.payload)[0], np.arange(cap,
                                                          dtype=np.float32))


class TestAlgorithmConformance:
    @pytest.mark.parametrize("seed,n,M", [(0, 300, 16), (1, 500, 32)])
    def test_sort_instances(self, seed, n, M):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=n).astype(np.float32))
        key = jax.random.PRNGKey(seed)
        results = [sample_sort_mr(x, M, engine=e, key=key) for e in instance_engines()]
        want = np.sort(np.asarray(x))
        for res, e in zip(results, instance_engines()):
            assert int(res.stats.dropped) == 0, e.name
            np.testing.assert_array_equal(np.asarray(res.values), want,
                                          err_msg=e.name)
            assert_same_accum(results[0].stats, res.stats, ctx=e.name)

    # Bucket-refinement ladders whose branching B > 2 does not divide V - 1
    # (V = 50, B = 8; V = 100, B = 5), float keys and int keys with
    # duplicates: every level's grouped lookup, on every substrate, gives
    # the ReferenceEngine's values and per-round stats.
    @pytest.mark.parametrize("seed,n,M,levels,dtype", [
        (5, 400, 8, 2, np.float32), (6, 400, 4, 3, np.int32)])
    def test_sort_ladder_instances(self, seed, n, M, levels, dtype):
        from repro.core import sort_plan
        rng = np.random.default_rng(seed)
        if dtype == np.int32:
            x = jnp.asarray(rng.integers(0, n // 3, n).astype(dtype))
        else:
            x = jnp.asarray(rng.normal(size=n).astype(dtype))
        key = jax.random.PRNGKey(seed)
        results = [e.compile(sort_plan(n, M, dtype=x.dtype, levels=levels,
                                       align=e.aligned_nodes))(x, key=key)
                   for e in instance_engines()]
        want = np.sort(np.asarray(x))
        for res, e in zip(results, instance_engines()):
            assert int(res.stats.dropped) == 0, e.name
            np.testing.assert_array_equal(np.asarray(res.values), want,
                                          err_msg=e.name)
            assert_same_accum(results[0].stats, res.stats, ctx=e.name)

    @pytest.mark.parametrize("seed,n,M", [(3, 120, 8), (4, 250, 32)])
    def test_hull2d_instances(self, seed, n, M):
        rng = np.random.default_rng(seed)
        pts = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        key = jax.random.PRNGKey(seed)
        results = [convex_hull_2d_mr(pts, M, engine=e, key=key)
                   for e in instance_engines()]
        ref = results[0]
        assert int(ref.count) >= 3
        for res, e in zip(results[1:], instance_engines()[1:]):
            np.testing.assert_array_equal(np.asarray(ref.points),
                                          np.asarray(res.points),
                                          err_msg=e.name)
            assert int(ref.count) == int(res.count), e.name
            assert_same_accum(ref.stats, res.stats, ctx=e.name)

    @pytest.mark.parametrize("seed,n,M", [(5, 12, 16), (6, 10, 8)])
    def test_hull3d_instances(self, seed, n, M):
        rng = np.random.default_rng(seed)
        pts = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        results = [convex_hull_3d_mr(pts, M, engine=e) for e in instance_engines()]
        ref = results[0]
        for res, e in zip(results[1:], instance_engines()[1:]):
            np.testing.assert_array_equal(np.asarray(ref.mask),
                                          np.asarray(res.mask),
                                          err_msg=e.name)
            assert_same_accum(ref.stats, res.stats, ctx=e.name)

    @pytest.mark.parametrize("seed,n,d,M", [(7, 10, 2, 16), (8, 8, 3, 8)])
    def test_lp_instances(self, seed, n, d, M):
        rng = np.random.default_rng(seed)
        A = rng.normal(size=(n, d)).astype(np.float32)
        b = rng.uniform(1, 2, n).astype(np.float32)   # origin feasible
        c = rng.normal(size=d).astype(np.float32)
        results = [linear_program_mr(c, A, b, M, engine=e) for e in instance_engines()]
        ref = results[0]
        assert np.isfinite(float(ref.objective))
        for res, e in zip(results[1:], instance_engines()[1:]):
            assert float(ref.objective) == float(res.objective), e.name
            np.testing.assert_array_equal(np.asarray(ref.x),
                                          np.asarray(res.x), err_msg=e.name)
            assert_same_accum(ref.stats, res.stats, ctx=e.name)


class TestOverlappedScheduleConformance:
    """DESIGN.md §13: the double-buffered sharded schedule must be
    *bit-identical* to the strictly-sequential comparator
    (``ShardedEngine(overlap=False)``) — mailbox values, validity, and the
    per-round CostAccum fold.  The schedule is value-agnostic (both paths
    issue the same two jitted programs per round in the same order; only
    the host's issue/sync timing differs), so parity must hold even for
    round programs whose destinations are data-dependent — i.e. programs
    that *overstate* ``early_dests``.  Axis size 1 runs in-process; axis
    sizes 2 and 4 run in a subprocess over mesh device subsets."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_program_overlap_parity(self, seed):
        rng = np.random.default_rng(seed)
        V = int(rng.integers(4, 10))
        cap = int(rng.integers(2, 5))
        n_rounds = 4
        entry_dests = rng.integers(-1, V, size=(V, cap)).astype(np.int32)
        payload = rng.normal(size=(V, cap)).astype(np.float32)
        tables = jnp.asarray(
            rng.integers(-1, V, size=(n_rounds, V, cap)).astype(np.int32))

        def fn(r, ids, box):
            dests = jnp.where(box.valid, tables[r], -1)
            return dests, box.payload

        ref_box = ref_acc = None
        for eng, early in [(ShardedEngine(overlap=False), False),
                           (ShardedEngine(overlap=False), True),
                           (ShardedEngine(), True),
                           (LocalEngine(), True)]:
            box, st = eng.shuffle(entry_dests, payload, V, cap)
            acc = CostAccum.zero().add_round_stats(st)
            box, acc = eng.run_rounds(fn, box, n_rounds, accum=acc,
                                      early_dests=early)
            if ref_box is None:
                ref_box, ref_acc = box, acc
            else:
                ctx = f"seed={seed} {eng.name} early={early}"
                assert_same_box(ref_box, box, ctx=ctx)
                assert_same_accum(ref_acc, acc, ctx=ctx)
        overlapped = ShardedEngine()
        overlapped.run_rounds(fn, ref_box, 1, accum=ref_acc,
                              early_dests=True)
        assert overlapped.route_log.overlapped == 1   # scheduler engaged

    @pytest.mark.parametrize("seed,n,M", [(0, 96, 8), (1, 64, 16)])
    def test_sort_overlap_parity(self, seed, n, M):
        from repro.core import sort_plan
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=n).astype(np.float32))
        key = jax.random.PRNGKey(seed)
        seq = ShardedEngine(overlap=False)
        ovl = ShardedEngine()
        res_s = seq.compile(sort_plan(n, M, align=seq.aligned_nodes))(
            x, key=key)
        res_o = ovl.compile(sort_plan(n, M, align=ovl.aligned_nodes))(
            x, key=key)
        np.testing.assert_array_equal(np.asarray(res_s.values),
                                      np.asarray(res_o.values))
        assert_same_accum(res_s.stats, res_o.stats, ctx="sort overlap")

    @pytest.mark.parametrize("seed,n,M", [(2, 64, 16)])
    def test_hull2d_overlap_parity(self, seed, n, M):
        from repro.core import hull2d_plan
        rng = np.random.default_rng(seed)
        pts = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        key = jax.random.PRNGKey(seed)
        seq = ShardedEngine(overlap=False)
        ovl = ShardedEngine()
        res_s = seq.compile(hull2d_plan(n, M, align=seq.aligned_nodes))(
            pts, key=key)
        res_o = ovl.compile(hull2d_plan(n, M, align=ovl.aligned_nodes))(
            pts, key=key)
        np.testing.assert_array_equal(np.asarray(res_s.points),
                                      np.asarray(res_o.points))
        assert int(res_s.count) == int(res_o.count)
        assert_same_accum(res_s.stats, res_o.stats, ctx="hull2d overlap")

    def test_pipeline_events_tracer_neutral(self):
        """pipeline.* events are pure telemetry: overlapped results are
        identical with the tracer on and off, the overlapped run emits
        pipeline.hop per round plus one pipeline.overlap per window, and
        the sequential comparator emits no pipeline.* events at all."""
        from repro.obs import Tracer
        rng = np.random.default_rng(3)
        V, cap, R = 6, 3, 4
        entry = rng.integers(-1, V, size=(V, cap)).astype(np.int32)
        payload = rng.normal(size=(V, cap)).astype(np.float32)
        node = jnp.arange(V, dtype=jnp.int32)[:, None]

        def fn(r, ids, box):
            return jnp.where(box.valid, (node + 1 + r) % V, -1), box.payload

        def run(eng):
            box, st = eng.shuffle(entry, payload, V, cap)
            return eng.run_rounds(fn, box, R,
                                  accum=CostAccum.zero().add_round_stats(st),
                                  early_dests=True)

        traced = ShardedEngine(tracer=Tracer())
        box_t, acc_t = run(traced)
        box_u, acc_u = run(ShardedEngine())                 # untraced
        box_s, acc_s = run(ShardedEngine(overlap=False,
                                         tracer=Tracer())) # sequential
        assert_same_box(box_s, box_t, ctx="traced overlap")
        assert_same_box(box_s, box_u, ctx="untraced overlap")
        assert_same_accum(acc_s, acc_t, ctx="traced overlap")
        assert_same_accum(acc_s, acc_u, ctx="untraced overlap")

        kinds = [e.kind for e in traced.tracer.events()]
        assert kinds.count("pipeline.hop") == R
        assert kinds.count("pipeline.overlap") == 1

    def test_multidevice_overlap_parity(self):
        """Axis sizes 2 and 4 under real cross-shard collectives (mesh over
        device subsets in one 4-device subprocess): random round program +
        the sort plan, overlapped vs sequential, values and CostAccum."""
        import os
        import subprocess
        import sys
        import textwrap
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = os.path.join(repo, "src")
        proc = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import CostAccum, ShardedEngine, sort_plan

        for n_sub in (2, 4):
            mesh = Mesh(np.array(jax.devices()[:n_sub]), ("nodes",))
            seq = ShardedEngine(mesh=mesh, overlap=False)
            ovl = ShardedEngine(mesh=mesh)
            rng = np.random.default_rng(n_sub)
            V, cap, R = seq.aligned_nodes(8), 3, 4
            entry = rng.integers(-1, V, size=(V, cap)).astype(np.int32)
            payload = rng.normal(size=(V, cap)).astype(np.float32)
            tables = jnp.asarray(
                rng.integers(-1, V, size=(R, V, cap)).astype(np.int32))
            def fn(r, ids, box):
                return jnp.where(box.valid, tables[r], -1), box.payload
            outs = []
            for eng, early in ((seq, False), (ovl, True)):
                box, st = eng.shuffle(entry, payload, V, cap)
                box, acc = eng.run_rounds(
                    fn, box, R, accum=CostAccum.zero().add_round_stats(st),
                    early_dests=early)
                outs.append((box, acc))
            (bs, as_), (bo, ao) = outs
            np.testing.assert_array_equal(np.asarray(bs.payload),
                                          np.asarray(bo.payload))
            np.testing.assert_array_equal(np.asarray(bs.valid),
                                          np.asarray(bo.valid))
            for a, b in zip(as_, ao):
                assert float(a) == float(b), (n_sub, a, b)
            assert ovl.route_log.overlapped == R

            key = jax.random.PRNGKey(0)
            x = jnp.asarray(rng.normal(size=32 * n_sub).astype(np.float32))
            rs = seq.compile(sort_plan(x.size, 8,
                                       align=seq.aligned_nodes))(x, key=key)
            ro = ovl.compile(sort_plan(x.size, 8,
                                       align=ovl.aligned_nodes))(x, key=key)
            np.testing.assert_array_equal(np.asarray(rs.values),
                                          np.asarray(ro.values))
            for a, b in zip(rs.stats, ro.stats):
                assert float(a) == float(b), (n_sub, a, b)
        print("OK")
        """)], capture_output=True, text=True, env=env, timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        assert "OK" in proc.stdout
