"""Engine-parity tests: one round program, identical results on all backends.

The tentpole guarantee of the unified MREngine API (DESIGN.md §2): a round
program produces bit-identical mailboxes and RoundStats on ReferenceEngine
(numpy oracle), LocalEngine (jnp, lax.scan) and ShardedEngine (shard_map,
axis size 1 in-process; multi-shard covered in test_distributed.py) —
including the shuffle's FIFO order and overflow/drop semantics.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (CostAccum, LocalEngine, Mailbox, MRCost,
                        ReferenceEngine, RoundProgram, ShardedEngine,
                        get_engine, multisearch_mr, run_rounds,
                        sample_sort_mr)

RNG = np.random.default_rng(7)


def engines():
    return [ReferenceEngine(), LocalEngine(), LocalEngine(use_scan=False),
            ShardedEngine()]


def assert_same_box(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a.payload),
                      jax.tree_util.tree_leaves(b.payload)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))


def assert_same_stats(a, b):
    for fa, fb in zip(a, b):
        assert float(fa) == float(fb), (a, b)


class TestShuffleParity:
    @pytest.mark.parametrize("n_nodes,m_out,cap", [(8, 4, 4), (16, 3, 2),
                                                   (4, 8, 16)])
    def test_mailbox_and_stats_identical(self, n_nodes, m_out, cap):
        dests = RNG.integers(-1, n_nodes, (n_nodes, m_out)).astype(np.int32)
        payload = np.arange(n_nodes * m_out,
                            dtype=np.float32).reshape(n_nodes, m_out)
        ref_box, ref_st = ReferenceEngine().shuffle(dests, payload,
                                                    n_nodes, cap)
        for e in engines()[1:]:
            box, st = e.shuffle(dests, payload, n_nodes, cap)
            assert_same_box(ref_box, box)
            assert_same_stats(ref_st, st)

    def test_overflow_drop_semantics(self):
        """All 16 items to node 0 with capacity 8: FIFO keeps the first 8
        (in flattened source order), drops exactly 8 — on every backend."""
        dests = np.zeros((4, 4), np.int32)
        payload = np.arange(16, dtype=np.float32).reshape(4, 4)
        for e in engines():
            box, st = e.shuffle(dests, payload, 4, 8)
            assert int(st.dropped) == 8, e.name
            assert int(st.max_received) == 16, e.name
            np.testing.assert_array_equal(np.asarray(box.payload[0]),
                                          np.arange(8.0))

    def test_pytree_payload(self):
        dests = RNG.integers(-1, 6, (6, 2)).astype(np.int32)
        payload = {"a": RNG.normal(size=(6, 2)).astype(np.float32),
                   "b": RNG.integers(0, 99, (6, 2, 3)).astype(np.int32)}
        ref_box, _ = ReferenceEngine().shuffle(dests, payload, 6, 4)
        for e in engines()[1:]:
            box, _ = e.shuffle(dests, payload, 6, 4)
            assert_same_box(ref_box, box)


class TestRoundProgramParity:
    def _program(self, V):
        def rotate(r, ids, box):
            dests = jnp.where(box.valid, (ids[:, None] + 1 + r) % V, -1)
            return dests, box.payload
        return RoundProgram(fn=rotate, n_rounds=3)

    def test_run_program_identical(self):
        V, cap = 8, 4
        dests = RNG.integers(0, V, (V, 2)).astype(np.int32)
        payload = np.arange(V * 2, dtype=np.float32).reshape(V, 2)
        prog = self._program(V)
        results = []
        for e in engines():
            box, _ = e.shuffle(dests, payload, V, cap)
            box, acc = e.run_program(prog, box)
            results.append((box, acc))
        for box, acc in results[1:]:
            assert_same_box(results[0][0], box)
            assert int(acc.rounds) == int(results[0][1].rounds)
            assert float(acc.communication) == float(
                results[0][1].communication)
            assert int(acc.dropped) == int(results[0][1].dropped)

    def test_local_engine_program_jits(self):
        """The whole run_program loop compiles: no host syncs inside."""
        V, cap = 8, 4
        prog = self._program(V)
        e = LocalEngine()
        dests = jnp.asarray(RNG.integers(0, V, (V, 2)).astype(np.int32))
        payload = jnp.arange(V * 2, dtype=jnp.float32).reshape(V, 2)

        @jax.jit
        def run(d, p):
            box, _ = e.shuffle(d, p, V, cap)
            return e.run_program(prog, box)

        box, acc = run(dests, payload)
        box2, acc2 = LocalEngine(use_scan=False).run_program(
            prog, e.shuffle(dests, payload, V, cap)[0])
        assert_same_box(box, box2)
        assert int(acc.rounds) == 3 and int(acc2.rounds) == 3

    def test_cost_accum_merge_laws(self):
        a = CostAccum.zero().add_round(10, 4).add_round(6, 2)
        b = CostAccum.zero().add_round(8, 8)
        par = a.merge_parallel(b)
        assert int(par.rounds) == 2 and float(par.communication) == 24.0
        assert int(par.max_reducer_io) == 8
        seq = a.merge_sequential(b)
        assert int(seq.rounds) == 3 and float(seq.internal_time) == 14.0
        # adapter round-trips into the mutable reporting object
        c = MRCost()
        c.absorb(seq)
        assert c.rounds == 3 and c.communication == 24


class TestAlgorithmParity:
    def test_sample_sort_three_backends(self):
        x = jnp.asarray(RNG.normal(size=800).astype(np.float32))
        key = jax.random.PRNGKey(11)
        results = [sample_sort_mr(x, 32, engine=e, key=key)
                   for e in engines()]
        want = np.sort(np.asarray(x))
        for res in results:
            assert int(res.stats.dropped) == 0
            np.testing.assert_array_equal(np.asarray(res.values), want)
        for res in results[1:]:
            assert int(res.stats.rounds) == int(results[0].stats.rounds)
            assert float(res.stats.communication) == float(
                results[0].stats.communication)

    def test_sample_sort_multilevel_radix(self):
        """levels=2: the recursion flattened to two engine refinement
        rounds still sorts and still agrees across backends."""
        x = jnp.asarray(RNG.normal(size=600).astype(np.float32))
        key = jax.random.PRNGKey(3)
        outs = [sample_sort_mr(x, 16, engine=e, key=key, levels=2)
                for e in (ReferenceEngine(), LocalEngine())]
        want = np.sort(np.asarray(x))
        for res in outs:
            assert int(res.stats.dropped) == 0
            np.testing.assert_array_equal(np.asarray(res.values), want)
        assert int(outs[0].stats.rounds) == int(outs[1].stats.rounds)

    def test_sample_sort_jit_no_host_syncs(self):
        """Acceptance: LocalEngine sample sort compiles under jax.jit (a
        host numpy op or int() sync inside would raise TracerError)."""
        x = jnp.asarray(RNG.normal(size=1024).astype(np.float32))
        fn = jax.jit(lambda v, k: sample_sort_mr(
            v, 32, engine=LocalEngine(), key=k))
        res = fn(x, jax.random.PRNGKey(0))
        assert int(res.stats.dropped) == 0
        np.testing.assert_array_equal(np.asarray(res.values),
                                      np.sort(np.asarray(x)))

    def test_multisearch_three_backends(self):
        q = jnp.asarray(RNG.normal(size=300).astype(np.float32))
        piv = jnp.sort(jnp.asarray(RNG.normal(size=60).astype(np.float32)))
        want = np.searchsorted(np.asarray(piv), np.asarray(q), side="left")
        results = [multisearch_mr(q, piv, 8, engine=e) for e in engines()]
        for res in results:
            np.testing.assert_array_equal(np.asarray(res.buckets), want)
        for res in results[1:]:
            assert int(res.stats.rounds) == int(results[0].stats.rounds)
            assert float(res.stats.communication) == float(
                results[0].stats.communication)

    def test_multisearch_capacity_drop_reporting(self):
        """With a tight capacity the w.h.p. overflow event is *reported*
        (identically on each backend), not a crash."""
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=64).astype(np.float32))
        piv = jnp.sort(jnp.asarray(rng.normal(size=10).astype(np.float32)))
        drops = [int(multisearch_mr(q, piv, 4, engine=e,
                                    capacity=2).stats.dropped)
                 for e in engines()]
        assert drops[0] > 0
        assert all(d == drops[0] for d in drops)

    def test_run_rounds_legacy_wrapper_raises_on_overflow(self):
        """Back-compat: mrmodel.run_rounds still enforces the strict model."""
        V = 4

        def all_to_zero(r, ids, box):
            return jnp.where(box.valid, 0, -1), box.payload

        e = LocalEngine()
        box, _ = e.shuffle(np.arange(16, dtype=np.int32) % V,
                           np.arange(16, dtype=np.float32), V, 4)
        with pytest.raises(RuntimeError, match="capacity"):
            run_rounds(all_to_zero, box, 1, cost=MRCost())

    def test_get_engine_factory(self):
        assert isinstance(get_engine("reference"), ReferenceEngine)
        assert isinstance(get_engine("local"), LocalEngine)
        with pytest.raises(ValueError):
            get_engine("bogus")


def test_quantile_splitters_past_int32_index_product():
    """The splitter positions (i * s) // V stay exact when (V - 1) * s
    passes int32 — 2^14 buckets x 32 samples each, a chip-sized sort."""
    from repro.core.sortmr import quantile_splitters
    n, V, oversample = 1 << 20, 1 << 14, 32
    x = np.random.default_rng(5).standard_normal(n, dtype=np.float32)
    key = jax.random.PRNGKey(5)
    splitters, s = quantile_splitters(jnp.asarray(x), V, oversample, key)
    assert s == V * oversample and (V - 1) * s > 2 ** 31
    sample = np.sort(x[np.asarray(jax.random.permutation(key, n))[:s]])
    want = sample[np.arange(1, V, dtype=np.int64) * s // V]
    np.testing.assert_array_equal(np.asarray(splitters), want)


@pytest.mark.parametrize("V", [2, 1 << 12, 1 << 14, 1 << 20])
def test_default_oversample_bounds_overflow(V):
    """The default samples per bucket hold the union-bounded Chernoff tail
    of any bucket passing ``slack`` times its share below 1e-9."""
    import math
    from repro.core.sortmr import default_oversample
    for slack in (1.5, 3.0, 8.0):
        k = default_oversample(V, slack)
        assert k >= 8
        if V > 1:
            assert V * math.exp(-k * (slack - 1 - math.log(slack))) <= 1e-9


@pytest.mark.parametrize("seed", [0, 1])
def test_sort_plan_defaults_do_not_drop_at_many_buckets(seed):
    """2^14 buckets: 8 samples per bucket (the old default) overflowed
    these seeds' buckets and dropped items; the default must not."""
    from repro.core import sort_plan
    n, M = 1 << 20, 64
    plan = sort_plan(n, M, levels=2)
    assert plan.n_nodes == 1 << 14
    x = np.random.default_rng(0).standard_normal(n, dtype=np.float32)
    res = LocalEngine().compile(plan)(jnp.asarray(x),
                                      key=jax.random.PRNGKey(seed))
    assert int(res.stats.dropped) == 0
    np.testing.assert_array_equal(np.asarray(res.values), np.sort(x))
