"""kernel_shuffle (multi-tile radix: fused counts → tile sort → scatter) vs
the dense oracle.

Bit-identity is the contract (DESIGN.md §7): same mailbox payload and
validity, same RoundStats values *and dtypes*, same drop set, for every
destination pattern the dense shuffle accepts — including overflow, all-
invalid, empty, and multi-leaf pytree payloads with trailing dims.  On CPU
the kernels run in interpret mode; the engine-level wiring
(``LocalEngine(shuffle_impl="kernel")`` / ``get_engine("pallas")`` /
``ShardedEngine(shuffle_impl="kernel")``) is exercised through scan and
shard_map round loops.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import CostAccum, LocalEngine, ShardedEngine, get_engine
from repro.core.kshuffle import kernel_fits, kernel_shuffle
from repro.core.mrmodel import shuffle as dense_shuffle


def assert_identical(res_dense, res_kernel, ctx=""):
    box_d, st_d = res_dense
    box_k, st_k = res_kernel
    for ld, lk in zip(jax.tree_util.tree_leaves(box_d.payload),
                      jax.tree_util.tree_leaves(box_k.payload)):
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lk),
                                      err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(box_d.valid),
                                  np.asarray(box_k.valid), err_msg=ctx)
    for name, fd, fk in zip(st_d._fields, st_d, st_k):
        assert int(fd) == int(fk), f"{ctx}: RoundStats.{name} {fd} != {fk}"
        assert np.asarray(fd).dtype == np.asarray(fk).dtype, \
            f"{ctx}: RoundStats.{name} dtype mismatch"


class TestKernelShuffleParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_random_1d(self, seed):
        rng = np.random.default_rng(seed)
        V = int(rng.integers(1, 24))
        cap = int(rng.integers(1, 6))
        n = int(rng.integers(0, 120))
        dests = jnp.asarray(rng.integers(-1, V, n).astype(np.int32))
        payload = {"x": jnp.asarray(rng.normal(size=n).astype(np.float32)),
                   "y": jnp.asarray(rng.integers(0, 99, (n, 2))
                                    .astype(np.int32))}
        assert_identical(dense_shuffle(dests, payload, V, cap),
                         kernel_shuffle(dests, payload, V, cap),
                         ctx=f"seed={seed} V={V} cap={cap} n={n}")

    @pytest.mark.parametrize("seed", range(2))
    def test_random_2d_mailbox_sends(self, seed):
        rng = np.random.default_rng(100 + seed)
        V, cap = int(rng.integers(2, 10)), int(rng.integers(1, 5))
        dests = jnp.asarray(rng.integers(-1, V, (V, cap)).astype(np.int32))
        payload = jnp.asarray(rng.normal(size=(V, cap)).astype(np.float32))
        assert_identical(dense_shuffle(dests, payload, V, cap),
                         kernel_shuffle(dests, payload, V, cap),
                         ctx=f"seed={seed}")

    def test_forced_overflow_fifo(self):
        """3x oversubscription: identical FIFO-kept prefix and drop count."""
        V, cap = 4, 3
        dests = jnp.asarray([0, 1, 0, 0, 1, 0, 1, 0, 1, 0, 1, 0],
                            dtype=jnp.int32)
        payload = jnp.arange(12, dtype=jnp.float32)
        res_k = kernel_shuffle(dests, payload, V, cap)
        assert int(res_k[1].dropped) == 6
        assert_identical(dense_shuffle(dests, payload, V, cap), res_k)

    def test_all_invalid_and_empty(self):
        V, cap = 5, 2
        for dests in (jnp.full((9,), -1, jnp.int32),
                      jnp.zeros((0,), jnp.int32)):
            payload = jnp.zeros(dests.shape, jnp.float32)
            res_k = kernel_shuffle(dests, payload, V, cap)
            assert int(res_k[1].items_sent) == 0
            assert not bool(np.asarray(res_k[0].valid).any())
            assert_identical(dense_shuffle(dests, payload, V, cap), res_k)

    def test_more_nodes_than_items(self):
        dests = jnp.asarray([7, 0, 7], jnp.int32)
        payload = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
        assert_identical(dense_shuffle(dests, payload, 64, 2),
                         kernel_shuffle(dests, payload, 64, 2))

    @pytest.mark.parametrize("tile_n", [1, 3, 8])
    def test_multi_tile_parity(self, tile_n):
        """Forcing tiny tiles crosses every tile boundary with small inputs:
        the cross-tile prefix (Thm 4.2 "send the counts") must stitch the
        per-tile FIFO ranks into the identical global order."""
        rng = np.random.default_rng(42 + tile_n)
        V, cap, n = 7, 3, 45
        dests = jnp.asarray(rng.integers(-1, V, n).astype(np.int32))
        payload = jnp.asarray(rng.normal(size=n).astype(np.float32))
        assert_identical(dense_shuffle(dests, payload, V, cap),
                         kernel_shuffle(dests, payload, V, cap,
                                        tile_n=tile_n),
                         ctx=f"tile_n={tile_n}")


class TestGuardBoundaries:
    """kernel_fits pinned at the exact guard edges (DESIGN.md §7).

    The old cliffs — single-VMEM-tile n <= 2^18 and the global int32 key
    space — are gone; the remaining guards (bucket count and sort row width
    within VMEM, count-matrix budget) are asserted on both sides of each
    boundary.  Pure predicate checks: nothing here executes a kernel at the
    big shapes.
    """

    def test_old_single_tile_cliff_gone(self):
        from repro.core.kshuffle import _MAX_SORT_N
        assert kernel_fits(_MAX_SORT_N - 1, 64)
        assert kernel_fits(_MAX_SORT_N, 64)
        assert kernel_fits(_MAX_SORT_N + 1, 64)

    def test_old_int32_key_cliff_gone(self):
        # Old global key dest*n_pad+src: 8192 * pow2ceil(300000) > 2^31.
        # Segmented per-tile keys stay at 8192 * 4096 — comfortably int32.
        assert kernel_fits(300000, 8191)

    def test_counts_budget_exact_edge(self):
        # V+1 = 1024 -> derived tile 4096 -> T <= 2^25/1024 = 32768 tiles,
        # i.e. n <= 32768 * 4096 = 2^27 exactly.
        assert kernel_fits(1 << 27, 1023)
        assert not kernel_fits((1 << 27) + 1, 1023)

    def test_min_tile_width_exact_edge(self):
        # The tile no longer shrinks with V; the node count itself is bounded
        # by the one-hot VMEM budget of bincount_tiles -> past it, bail dense.
        from repro.kernels.bincount import MAX_BUCKETS
        assert kernel_fits(100, MAX_BUCKETS)
        assert not kernel_fits(100, MAX_BUCKETS + 1)

    def test_explicit_tile_int32_edge(self):
        # An explicit tile_n must fit one bitonic row block in VMEM, which
        # (with the bucket bound) keeps (V+1)*tile_n within int32.
        from repro.kernels.bitonic_sort import MAX_ROW_WIDTH
        w = MAX_ROW_WIDTH
        assert kernel_fits(512, (1 << 15) - 1, tile_n=w)
        assert (1 << 15) * w < 2 ** 31 - 1
        assert not kernel_fits(512, (1 << 15) - 1, tile_n=w + 1)

    def test_empty_input_fits_iff_tile_does(self):
        assert kernel_fits(0, 5)
        assert not kernel_fits(0, 1 << 22)

    def test_strict_guard_raises_key_space(self):
        with pytest.raises(ValueError, match="one-hot VMEM budget"):
            kernel_shuffle(jnp.zeros((8,), jnp.int32),
                           jnp.zeros((8,), jnp.float32), 1 << 22, 4)

    def test_strict_guard_raises_counts_budget(self):
        with pytest.raises(ValueError, match="counts budget"):
            kernel_shuffle(jnp.zeros((40000,), jnp.int32),
                           jnp.zeros((40000,), jnp.float32), 8191, 4,
                           tile_n=8)

    def test_strict_guard_is_the_predicate(self):
        """One predicate, two policies: _check_fits raises exactly where
        kernel_fits is False."""
        from repro.core.kshuffle import _check_fits
        cases = [(100, 8, None), (0, 5, None), ((1 << 18) + 1, 64, None),
                 (40000, 2 ** 16, None), (70000, 2 ** 16, None),
                 (1 << 27, 1023, None), ((1 << 27) + 1, 1023, None),
                 (100, (1 << 21) - 1, None), (100, 1 << 21, None),
                 (512, (1 << 21) - 1, 512), (512, (1 << 21) - 1, 1024),
                 (200, (1 << 21) - 1, 8), (100, 1 << 15, None),
                 (100, (1 << 15) + 1, None), (512, 5, 8192),
                 (512, 5, 8193), (40000, 8191, 8)]
        for n, V, t in cases:
            raised = False
            try:
                _check_fits(n, V, t)
            except ValueError:
                raised = True
            assert raised == (not kernel_fits(n, V, t)), (n, V, t)

    def test_multi_tile_path_actually_taken(self):
        """Regression: a shape past the old single-tile cliff must route
        through the kernel (route_log), not silently fall back to dense."""
        from repro.core.kshuffle import _MAX_SORT_N
        rng = np.random.default_rng(3)
        n, V, cap = _MAX_SORT_N + 64, 16, 20000
        dests = jnp.asarray(rng.integers(-1, V, n).astype(np.int32))
        payload = jnp.asarray(rng.normal(size=n).astype(np.float32))
        eng = get_engine("pallas")
        eng.route_log.reset()
        got = eng.shuffle(dests, payload, V, cap)
        assert eng.route_log.snapshot() == (1, 0)
        assert_identical(LocalEngine().shuffle(dests, payload, V, cap), got,
                         ctx="past-old-cliff")


class TestDifferentialFuzz:
    """Seeded random differential suite: kernel vs dense oracle across both
    sides of every guard boundary — single vs multi-tile (tile_n forced
    tiny), all destination patterns the dense shuffle accepts, Local and
    per-shard Sharded."""

    PATTERNS = ("uniform", "all_same", "all_invalid", "overflow",
                "more_nodes", "empty_2d")

    @staticmethod
    def _case(seed):
        rng = np.random.default_rng(seed)
        pattern = TestDifferentialFuzz.PATTERNS[
            seed % len(TestDifferentialFuzz.PATTERNS)]
        V = int(rng.integers(1, 24))
        cap = int(rng.integers(1, 6))
        n = int(rng.integers(0, 300))
        if pattern == "uniform":
            dests = rng.integers(-1, V, n)
        elif pattern == "all_same":
            dests = np.full(n, int(rng.integers(0, V)))
        elif pattern == "all_invalid":
            dests = np.full(n, -1)
        elif pattern == "overflow":
            V, cap = int(rng.integers(1, 4)), 1
            dests = rng.integers(-1, V, n)
        elif pattern == "more_nodes":
            V, n = 300, int(rng.integers(0, 40))
            dests = rng.integers(-1, V, n)
        else:                                    # empty_2d: (0, M) sends
            dests = np.zeros((0, int(rng.integers(1, 5))))
        dests = jnp.asarray(dests.astype(np.int32))
        payload = {
            "x": jnp.asarray(rng.normal(size=dests.shape).astype(np.float32)),
            "y": jnp.asarray(rng.integers(0, 99, dests.shape + (2,))
                             .astype(np.int32))}
        return dests, payload, V, cap

    @pytest.mark.parametrize("seed", range(18))
    def test_fuzz_local(self, seed):
        dests, payload, V, cap = self._case(seed)
        tile_n = (None, 8, 32)[seed % 3]
        assert_identical(
            dense_shuffle(dests, payload, V, cap),
            kernel_shuffle(dests, payload, V, cap, tile_n=tile_n),
            ctx=f"seed={seed} V={V} cap={cap} shape={dests.shape} "
                f"tile_n={tile_n}")

    @pytest.mark.parametrize("seed", [0, 1, 3, 4])
    def test_fuzz_sharded(self, seed):
        """Same cases through the shard_map route: per-shard kernel scatter
        vs per-shard dense scatter, bit-identical stats included."""
        dests, payload, V, cap = self._case(seed)
        V = ShardedEngine().aligned_nodes(V)
        assert_identical(
            ShardedEngine().shuffle(dests, payload, V, cap),
            ShardedEngine(shuffle_impl="kernel").shuffle(dests, payload,
                                                         V, cap),
            ctx=f"sharded seed={seed} V={V} cap={cap}")


class TestShardedPerLevelRouting:
    def test_late_levels_route_through_kernel(self, monkeypatch):
        """The guard is re-derived per call (not baked in at _build time):
        with the counts budget shrunk so the entry shape cannot fit, a
        later, smaller call in the same engine still takes the kernel path
        — the shape-scheduled programs' shrinking levels stay kernel-backed.
        """
        from repro.core import kshuffle as K
        V, cap = 8, 4
        tile = K._TILE_N                         # default width (4096)
        # Budget admits exactly one tile of counts: n <= tile fits,
        # n > tile does not.
        monkeypatch.setattr(K, "_COUNTS_BUDGET", V + 1)
        rng = np.random.default_rng(9)
        big = jnp.asarray(rng.integers(-1, V, 2 * tile).astype(np.int32))
        small = jnp.asarray(rng.integers(-1, V, 64).astype(np.int32))
        eng = ShardedEngine(shuffle_impl="kernel")
        oracle = ShardedEngine()
        eng.route_log.reset()
        for d in (big, small):
            p = jnp.arange(d.shape[0], dtype=jnp.float32)
            assert_identical(oracle.shuffle(d, p, V, cap),
                             eng.shuffle(d, p, V, cap),
                             ctx=f"n={d.shape[0]}")
        assert eng.route_log.snapshot() == (1, 1)

    def test_local_engine_per_call_guard(self, monkeypatch):
        """LocalEngine('pallas') falls back to dense past the budget and
        returns to the kernel below it, bit-identically, same instance."""
        from repro.core import kshuffle as K
        V, cap = 8, 4
        tile = K._TILE_N
        monkeypatch.setattr(K, "_COUNTS_BUDGET", V + 1)
        rng = np.random.default_rng(10)
        eng = get_engine("pallas")
        oracle = LocalEngine()
        eng.route_log.reset()
        for n in (2 * tile, 64):
            d = jnp.asarray(rng.integers(-1, V, n).astype(np.int32))
            p = jnp.arange(n, dtype=jnp.float32)
            assert_identical(oracle.shuffle(d, p, V, cap),
                             eng.shuffle(d, p, V, cap), ctx=f"n={n}")
        assert eng.route_log.snapshot() == (1, 1)


class TestEngineWiring:
    def test_get_engine_pallas_alias(self):
        eng = get_engine("pallas")
        assert isinstance(eng, LocalEngine)
        assert eng.shuffle_impl == "kernel" and eng.name == "pallas"
        with pytest.raises(ValueError, match="shuffle_impl"):
            LocalEngine(shuffle_impl="fused")

    def test_scan_round_loop_parity(self):
        """Whole multi-round programs under lax.scan match the dense engine,
        mailbox and CostAccum alike."""
        rng = np.random.default_rng(7)
        V, cap, R = 8, 3, 4
        entry = jnp.asarray(rng.integers(-1, V, (V, cap)).astype(np.int32))
        payload = jnp.asarray(rng.normal(size=(V, cap)).astype(np.float32))
        tables = jnp.asarray(rng.integers(-1, V, (R, V, cap)).astype(np.int32))

        def fn(r, ids, box):
            return jnp.where(box.valid, tables[r], -1), box.payload

        outs = []
        for eng in (LocalEngine(), get_engine("pallas"),
                    LocalEngine(use_scan=False, shuffle_impl="kernel")):
            box, st = eng.shuffle(entry, payload, V, cap)
            box, acc = eng.run_rounds(fn, box, R,
                                      accum=CostAccum.zero()
                                      .add_round_stats(st))
            outs.append((box, acc))
        for box, acc in outs[1:]:
            np.testing.assert_array_equal(np.asarray(outs[0][0].payload),
                                          np.asarray(box.payload))
            np.testing.assert_array_equal(np.asarray(outs[0][0].valid),
                                          np.asarray(box.valid))
            for fa, fb in zip(outs[0][1], acc):
                assert float(fa) == float(fb)

    def test_sharded_kernel_scatter_parity(self):
        """ShardedEngine(shuffle_impl='kernel'): the per-shard local scatter
        runs the Pallas path inside shard_map (check_vma relaxed)."""
        rng = np.random.default_rng(11)
        V, cap = 8, 3
        dests = jnp.asarray(rng.integers(-1, V, 40).astype(np.int32))
        payload = jnp.asarray(rng.normal(size=40).astype(np.float32))
        want = ShardedEngine().shuffle(dests, payload, V, cap)
        got = ShardedEngine(shuffle_impl="kernel").shuffle(dests, payload,
                                                           V, cap)
        assert_identical(want, got)
